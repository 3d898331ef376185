"""The per-query span and counter record (``repro.runtime.trace``) on a CPU
engine: the span tree of a hit and of a miss wave, the ``RunInfo.t_*``
fields read off it, the device-to-host counters, compiles by function name,
and the same spans in a ``jax.profiler`` trace on the profiler's clock."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Aggregate, Database, Having, PBDSEngine, Query
from repro.core.datasets import make_crimes
from repro.core.queries import execute
from repro.core.strategies import SelectionConfig
from repro.core.table import PAD_VALID
from repro.runtime import trace
from repro.runtime.shapes import group_class, row_class

HIT_CHILDREN = ["repro.probe", "repro.repair", "repro.instance", "repro.where",
                "repro.groups", "repro.aggregate", "repro.to_host", "repro.to_host",
                "repro.to_host", "repro.having"]


@pytest.fixture(scope="module")
def db():
    return Database({"crimes": make_crimes(12_000, seed=5)})


def _q(db, groupby, fn, attr, quantile):
    base = Query("crimes", groupby, Aggregate(fn, attr))
    thr = float(np.quantile(execute(base, db).values, quantile))
    return Query("crimes", groupby, Aggregate(fn, attr), having=Having(">", thr))


@pytest.fixture(scope="module")
def queries(db):
    # The first two share an inner block and neither subsumes the other (the
    # higher threshold comes first); the third has a block of its own.
    return [_q(db, ("district", "year"), "count", None, 0.9),
            _q(db, ("district", "year"), "count", None, 0.5),
            _q(db, ("ward",), "sum", "records", 0.8)]


@pytest.fixture(scope="module")
def served(db, queries):
    """One miss wave that captures three sketches, then a batch of hits."""
    eng = PBDSEngine(db)
    wave = eng.run_batch(queries)
    hits = eng.run_batch([queries[2], queries[0], queries[1]])
    return eng, wave, hits


def _root(span):
    while span.parent is not None:
        span = span.parent
    return span


def test_hit_span_tree(served):
    _, wave, hits = served
    batch_span = _root(hits[0][1].trace.spans[0])
    assert batch_span.name == "repro.run_batch"
    for pos, (_, info) in enumerate(hits):
        rec = info.trace
        assert info.reused and rec.pos == pos and rec.batch == hits[0][1].trace.batch
        serve = rec.spans[0]
        assert serve.name == "repro.serve" and serve.parent is batch_span
        assert [s.name for s in rec.spans[1:]] == HIT_CHILDREN
        assert all(s.parent is serve for s in rec.spans[1:])
        assert all(serve.start_ns <= s.start_ns <= s.end_ns <= serve.end_ns
                   for s in rec.spans[1:])
        assert rec.shared == [] and rec.catalog_misses == 0 and rec.compiled == []
        # Nothing of the wave or of another query leaks into the record.
        others = {id(s) for j, (_, o) in enumerate(hits) if j != pos for s in o.trace.spans}
        others |= {id(s) for _, o in wave for s in o.trace.spans}
        assert not others & {id(s) for s in rec.spans}
    assert len({info.trace.batch for _, info in wave} | {hits[0][1].trace.batch}) == 2
    # Serves run one after another inside the call.
    serves = [info.trace.spans[0] for _, info in hits]
    assert all(a.end_ns <= b.start_ns for a, b in zip(serves, serves[1:]))


def test_hit_times_are_its_spans(served):
    for _, info in served[2]:
        rec = info.trace
        probe, repair, serve = (rec.last(n) for n in
                                ("repro.probe", "repro.repair", "repro.serve"))
        assert info.t_probe == probe.seconds
        assert info.t_repair == repair.seconds
        assert info.t_execute == (serve.end_ns - repair.end_ns) / 1e9
        assert info.t_select == info.t_capture == 0.0


def test_hit_copies_three_arrays(served, queries):
    eng, _, hits = served
    for q, (_, info) in zip([queries[2], queries[0], queries[1]], hits):
        entry = eng.index.lookup_entry(q)
        inst = eng.catalog.get_instance(entry.sketch, eng.db["crimes"])
        n_groups = eng.catalog.groups(inst, q.groupby).n_groups
        # counts and aggregate (float32 per group), and the WHERE mask (one
        # byte per padded instance row)
        assert info.trace.host_syncs == 3
        assert info.trace.d2h_bytes == 2 * 4 * n_groups + inst.num_rows


def test_hit_counts_the_rows_and_groups_it_aggregates(served, queries):
    """A hit's record counts the instance's real rows and its groups, and
    the size classes its aggregate ran them at; the miss wave's two shared
    scans count the whole table twice on the batch's record."""
    eng, wave, hits = served
    for q, (_, info) in zip([queries[2], queries[0], queries[1]], hits):
        sketch = eng.index.lookup_entry(q).sketch
        inst = eng.catalog.get_instance(sketch, eng.db["crimes"])
        n_groups = eng.catalog.groups(inst, q.groupby).n_groups
        rec = info.trace
        assert rec.agg_rows == sketch.size_rows == int(np.asarray(inst[PAD_VALID]).sum())
        assert rec.agg_rows_padded == row_class(sketch.size_rows) == inst.num_rows
        assert (rec.agg_groups, rec.agg_groups_padded) == (n_groups, group_class(n_groups))
        assert rec.outer.agg_rows == 0
    n = eng.db["crimes"].num_rows
    outer = wave[0][1].trace.outer
    assert (outer.agg_rows, outer.agg_rows_padded) == (2 * n, 2 * row_class(n))


def test_miss_wave_span_tree(served):
    _, wave, _ = served
    recs = [info.trace for _, info in wave]
    assert all(info.created for _, info in wave)
    batch_span = _root(recs[0].spans[0])
    select = {id(s): s for r in recs for s, _ in r.shared if s.name == "repro.select"}
    scans = {id(s): s for r in recs for s, _ in r.shared if s.name == "repro.scan"}
    bitmaps = {id(s): s for r in recs for s, _ in r.shared
               if s.name == "repro.capture.bitmap"}
    assert len(select) == 1 and len(scans) == 2 and len(bitmaps) == 1
    (select,), (bitmap,) = select.values(), bitmaps.values()
    assert select.parent is batch_span
    assert all(s.parent is batch_span for s in scans.values())
    assert bitmap.parent.name == "repro.capture" and bitmap.parent.parent is batch_span
    capture = bitmap.parent
    for pos, rec in enumerate(recs):
        assert rec.pos == pos
        assert [s.name for s in rec.spans[:2]] == ["repro.serve", "repro.probe"]
        assert rec.spans[0].end_ns <= select.start_ns  # the serve held only the probe
        assert [s.name for s in rec.spans if s.parent in (batch_span, capture)] == [
            "repro.serve", "repro.finish", "repro.capture.maintainer",
            "repro.capture.warm"]
        # Each span's parent is in this record or is a span of the batch.
        own = {id(s) for s in rec.spans}
        assert all(id(s.parent) in own or s.parent in (batch_span, capture)
                   for s in rec.spans)
        having = next(s for s in rec.spans if s.name == "repro.having")
        assert having.parent is rec.last("repro.finish")


def test_miss_times_are_their_spans(served):
    _, wave, _ = served
    for _, info in wave:
        rec = info.trace
        shares = {s.name: (s, n) for s, n in rec.shared}
        select, n_wave = shares["repro.select"]
        scan, n_scan = shares["repro.scan"]
        bitmap, n_cap = shares["repro.capture.bitmap"]
        assert n_wave == n_cap == 3
        assert info.t_probe == rec.last("repro.probe").seconds
        assert info.t_select == select.seconds / 3
        assert info.t_execute == scan.seconds / n_scan + rec.last("repro.finish").seconds
        assert info.t_capture == pytest.approx(
            bitmap.seconds / 3 + rec.last("repro.capture.maintainer").seconds
            + rec.last("repro.capture.warm").seconds, rel=0, abs=1e-12)


def test_run_spans_and_times(db):
    eng = PBDSEngine(db)
    q = _q(db, ("district", "month"), "count", None, 0.9)
    _, miss = eng.run(q)
    rec = miss.trace
    assert miss.created and rec.pos == 0
    tops = [s.name for s in rec.spans if s.parent is None]
    assert tops == ["repro.serve", "repro.select", "repro.capture", "repro.scan",
                    "repro.finish", "repro.capture"]
    captures = [s for s in rec.spans if s.name == "repro.capture"]
    assert [s.name for s in rec.spans if s.parent is captures[1]] == [
        "repro.capture.bitmap", "repro.capture.maintainer", "repro.capture.warm"]
    assert miss.t_select == rec.last("repro.select").seconds
    assert miss.t_capture == sum(s.seconds for s in captures)
    assert miss.t_execute == rec.last("repro.scan").seconds + rec.last("repro.finish").seconds
    _, hit = eng.run(q)
    assert hit.reused and [s.name for s in hit.trace.spans] == ["repro.serve"] + HIT_CHILDREN
    assert hit.trace.batch != rec.batch


def test_d2h_bytes_are_the_arrays_copied():
    rec = trace.query_record(0)
    arrays = [jnp.arange(10, dtype=jnp.float32), jnp.ones((3, 5), bool),
              jnp.zeros(7, jnp.int32)]
    with trace.recording(rec):
        copies = [trace.to_host(a) for a in arrays]
        trace.to_host(np.ones(4))  # already on the host: no sync
    assert rec.host_syncs == 3
    assert rec.d2h_bytes == sum(c.nbytes for c in copies) == 40 + 15 + 28
    assert [s.name for s in rec.spans] == ["repro.to_host"] * 4


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_forced_recompile_is_recorded_by_name(served, queries, no_persistent_cache):
    eng = served[0]
    eng.run_batch([queries[2]])  # warm
    jax.clear_caches()
    (_, info), = eng.run_batch([queries[2]])
    assert info.reused
    assert any("_segment_aggregate_jit" in name for name in info.trace.compiled)
    (_, again), = eng.run_batch([queries[2]])
    assert again.trace.compiled == []


def _profiled_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    data = ProfileData.from_file(path)
    start = None
    events = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    events.append((line, e.name, e.start_ns, e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    assert start is not None
    out = []
    for line, name, a, b, args in events:
        # The innermost repro.* event of the same thread that holds this one.
        holders = [o for o in events if o[0] is line and o[2] <= a and b <= o[3]
                   and (o[2], o[3], o[1]) != (a, b, name)]
        parent = max(holders, key=lambda o: o[2])[1] if holders else None
        out.append((name, start + a, parent, args))
    return out


def test_profiler_trace_holds_the_record_spans(db, queries, tmp_path):
    from jax.profiler import ProfileOptions

    # Paper-faithful selection estimates every candidate: the wave samples.
    eng = PBDSEngine(db, selection=SelectionConfig.paper_faithful())
    fresh = _q(db, ("district", "month"), "count", None, 0.9)
    eng.run_batch(queries[:1])
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = eng.run_batch([queries[0], fresh])
    finally:
        jax.profiler.stop_trace()
    events = _profiled_events(tmp_path)
    recs = [info.trace for _, info in out]
    batch_span = _root(recs[0].spans[0])
    spans = [(batch_span, recs[0].batch, None)]
    spans += [(s, r.batch, r.pos) for r in recs for s in r.spans]
    for r in recs:
        for s, _ in r.shared:
            while s is not batch_span:
                spans.append((s, recs[0].batch, None))
                s = s.parent
    seen = set()
    for s, batch, pos in spans:
        if id(s) in seen:
            continue
        seen.add(id(s))
        want = {"batch": batch} if pos is None else {"batch": batch, "pos": pos}
        match = [e for e in events if e[0] == s.name and e[3] == want
                 and abs(e[1] - s.start_ns) < 1_000_000]
        assert match, (s.name, want)
        parent = s.parent.name if s.parent is not None else None
        assert any(e[2] == parent for e in match), (s.name, parent, match)
    # The batch's own spans hold the wave's work, each under its parent.
    batch_only = {"batch": recs[0].batch}
    under = lambda p: sorted(e[0] for e in events if e[2] == p and e[3] == batch_only)  # noqa: E731
    assert under("repro.scan") == sorted(["repro.where", "repro.groups", "repro.aggregate"]
                                         + ["repro.to_host"] * 3)
    assert under("repro.select") == ["repro.select.aqr", "repro.select.estimate",
                                     "repro.select.sample"]
    assert under("repro.capture") == ["repro.capture.bitmap"]


@pytest.fixture(scope="module")
def star():
    """Sales joined to stores; a fifth of the sales have no store, and the
    first ten days sell more, so a sketch on ``s_day`` pays."""
    from repro.core.table import from_numpy

    rng = np.random.default_rng(3)
    day = rng.integers(1, 40, 4000)
    return Database({
        "sales": from_numpy("sales", {
            "s_store": rng.integers(1, 121, 4000).astype(np.int32),
            "s_day": day.astype(np.int32),
            "s_amount": (rng.random(4000) * np.where(day <= 10, 50, 10)).astype(np.float32)}),
        "stores": from_numpy("stores", {
            "st_key": np.arange(1, 101, dtype=np.int32),
            "st_region": rng.integers(1, 9, 100).astype(np.int32)}),
    })


def _join_q(db, quantile):
    from repro.core import JoinSpec

    base = Query("sales", ("s_day",), Aggregate("sum", "s_amount"),
                 join=JoinSpec("stores", "s_store", "st_key"))
    thr = float(np.quantile(execute(base, db).values, quantile))
    return Query(base.table, base.groupby, base.agg, join=base.join, having=Having(">", thr))


def _joins(rec):
    return [s for s in rec.spans if s.name == "repro.join"]


def _nbytes(table):
    return sum(int(v.nbytes) for v in table.columns.values())


@pytest.mark.parametrize("batched", [False, True])
def test_join_capture_records_its_join_spans_and_counters(star, batched):
    """A capture over a join: its scan builds the join layout (one
    ``repro.join`` under ``repro.scan``: on the query's record from ``run``,
    on the batch's from ``run_batch``) and its warm-up gathers the joined
    sketch instance (one under ``repro.capture.warm``).  The counters hold
    the rows and bytes of what was materialized.  A hit over the cached
    joined instance records none."""
    eng = PBDSEngine(star)
    q = _join_q(star, 0.5)
    _, miss = eng.run_batch([q])[0] if batched else eng.run(q)
    rec = miss.trace
    assert miss.created and eng.catalog.stats["join_instance"] == 1
    layout, _ = eng.catalog.join(star["sales"], star["stores"], "s_store", "st_key")
    inst = eng.catalog.get_instance(eng.index.lookup_entry(q).sketch, star["sales"])
    joined, _ = eng.catalog.join(inst, star["stores"], "s_store", "st_key")
    assert eng.catalog.stats["join_hit"] >= 2  # both were cached
    scan_rec = rec.outer if batched else rec
    assert (rec.outer is not None) == batched
    under = lambda r, name: [s for s in _joins(r) if s.parent.name == name]  # noqa: E731
    assert len(under(scan_rec, "repro.scan")) == len(under(rec, "repro.capture.warm")) == 1
    assert len(_joins(rec)) == (1 if batched else 2)
    assert (scan_rec.join_rows, scan_rec.join_bytes) == (
        (layout.num_rows, _nbytes(layout)) if batched else
        (layout.num_rows + joined.num_rows, _nbytes(layout) + _nbytes(joined)))
    if batched:
        assert (rec.join_rows, rec.join_bytes) == (joined.num_rows, _nbytes(joined))
        assert (rec.join_derived, rec.outer.join_derived) == (1, 0)
    else:
        assert rec.join_derived == 1
    assert 0 < layout.num_rows < star["sales"].num_rows  # unmatched sales dropped

    _, hit = eng.run_batch([_join_q(star, 0.8)])[0]
    assert hit.reused and _joins(hit.trace) == []
    assert (hit.trace.join_rows, hit.trace.join_bytes, hit.trace.join_derived) == (0, 0, 0)
    assert _joins(hit.trace.outer) == [] and hit.trace.outer.join_rows == 0


def test_join_hit_counts_the_joined_instances_real_rows(star):
    """Over a joined instance a hit counts the joined rows that come from
    the instance's real rows (sales without a store are gone, padding rows
    are kept), and the row class of all of them."""
    eng = PBDSEngine(star)
    eng.run_batch([_join_q(star, 0.5)])
    q = _join_q(star, 0.8)
    _, hit = eng.run_batch([q])[0]
    assert hit.reused
    sketch = eng.index.lookup_entry(q).sketch
    inst = eng.catalog.get_instance(sketch, star["sales"])
    joined, _ = eng.catalog.join(inst, star["stores"], "s_store", "st_key")
    real = int(np.asarray(joined[PAD_VALID]).sum())
    assert 0 < real < sketch.size_rows and joined.num_rows > real
    assert hit.trace.agg_rows == real == eng.catalog.valid_rows(joined)
    assert hit.trace.agg_rows_padded == row_class(joined.num_rows)
