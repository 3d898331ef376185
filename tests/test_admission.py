"""Differential suite for the batched admission pipeline.

The contract under test: ``PBDSEngine.run_batch(qs)`` is *bit-for-bit*
equivalent to ``[engine.run(q) for q in qs]`` — query results, index
contents (which sketches exist, their bits and sizes), and post-mutation
maintainer state — while sharing the miss-path work (one sample + one AQR
pass + one inner-block scan + one capture launch per signature group).

Also covered: the batched capture kernel against the per-mask oracle, the
multi-query padded estimator against the single-query path, and the
steady-state recompile guarantee (row-class-padded instances + pow2-quantized
selection shapes => zero new XLA compilations after warmup).
"""
import contextlib
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (
    Aggregate,
    Database,
    Having,
    JoinSpec,
    Query,
    execute,
)
from repro.core.datasets import make_crimes, make_tpch
from repro.core.engine import PBDSEngine
from repro.core.strategies import SelectionConfig
from repro.runtime.guards import retrace_guard

N_ROWS = 30_000


@contextlib.contextmanager
def count_xla_compiles():
    """Count real backend compilations via the shared retrace guard
    (cached executions emit no event)."""
    with retrace_guard(allowed=None) as watch:
        yield watch.events


@pytest.fixture(scope="module")
def tpch_db():
    return make_tpch(N_ROWS, seed=7)


def _threshold(q: Query, db: Database, quantile: float) -> float:
    vals = execute(dataclasses.replace(q, having=None, outer_having=None), db).values
    return float(np.quantile(vals, quantile))


def _template_batches(db: Database, quantiles):
    """Per template, a batch of queries differing only in HAVING thresholds.

    Thresholds descend so earlier queries do NOT subsume later ones (every
    query admits); duplicates and ascending pairs are added by the callers
    that exercise the deferral/hit paths.
    """
    batches = {}

    agh = Query("lineitem", ("l_suppkey",), Aggregate("sum", "l_quantity"))
    batches["Q-AGH"] = [
        dataclasses.replace(agh, having=Having(">", _threshold(agh, db, qt)))
        for qt in quantiles
    ]

    ajgh = Query(
        "lineitem", ("l_suppkey",), Aggregate("sum", "l_quantity"),
        join=JoinSpec("orders", "l_orderkey", "o_orderkey"),
    )
    batches["Q-AJGH"] = [
        dataclasses.replace(ajgh, having=Having(">", _threshold(ajgh, db, qt)))
        for qt in quantiles
    ]

    # Nested templates vary the *inner* threshold (what selection estimates
    # see — Alg. 1 runs over the inner block) so admission actually happens.
    inner = Query("lineitem", ("l_suppkey", "l_partkey"),
                  Aggregate("sum", "l_quantity"))
    batches["Q-AAGH"] = [
        dataclasses.replace(
            inner, having=Having(">", _threshold(inner, db, qt)),
            outer_groupby=("l_suppkey",), outer_agg=Aggregate("sum", None),
            outer_having=Having(">", 0.0))
        for qt in quantiles
    ]

    inner_j = dataclasses.replace(
        inner, join=JoinSpec("orders", "l_orderkey", "o_orderkey"))
    batches["Q-AAJGH"] = [
        dataclasses.replace(
            inner_j, having=Having(">", _threshold(inner_j, db, qt)),
            outer_groupby=("l_suppkey",), outer_agg=Aggregate("sum", None),
            outer_having=Having(">", 0.0))
        for qt in quantiles
    ]
    return batches


def _engines(db, **kw):
    args = dict(strategy="CB-OPT-GB", n_ranges=40, theta=0.1, seed=0,
                min_selectivity_gain=0.98)
    args.update(kw)
    return PBDSEngine(db, **args), PBDSEngine(db, **args)


def _assert_run_parity(seq, bat, ctx=""):
    assert len(seq) == len(bat)
    for i, (s, b) in enumerate(zip(seq, bat)):
        assert s[0].canonical() == b[0].canonical(), f"{ctx} result {i}"
        assert (s[1].reused, s[1].created, s[1].repaired, s[1].attr) == (
            b[1].reused, b[1].created, b[1].repaired, b[1].attr), f"{ctx} info {i}"


def _assert_index_parity(e_seq, e_bat, ctx=""):
    es = sorted(e_seq.index.entries(), key=lambda e: repr(e.query.signature()))
    eb = sorted(e_bat.index.entries(), key=lambda e: repr(e.query.signature()))
    assert len(es) == len(eb), f"{ctx}: {len(es)} vs {len(eb)} entries"
    for a, b in zip(es, eb):
        assert a.query.signature() == b.query.signature(), ctx
        np.testing.assert_array_equal(a.sketch.bits, b.sketch.bits, err_msg=ctx)
        assert a.sketch.size_rows == b.sketch.size_rows, ctx
        assert a.sketch.attr == b.sketch.attr, ctx
        ma, mb = a.maintainer, b.maintainer
        assert (ma is None) == (mb is None), ctx
        if ma is not None:
            np.testing.assert_array_equal(ma.frag_prov, mb.frag_prov, err_msg=ctx)
            np.testing.assert_array_equal(ma.sums, mb.sums, err_msg=ctx)
            np.testing.assert_array_equal(ma.counts, mb.counts, err_msg=ctx)
            np.testing.assert_array_equal(ma.passing, mb.passing, err_msg=ctx)
            assert ma.conservative == mb.conservative, ctx


@pytest.mark.parametrize("template", ["Q-AGH", "Q-AJGH", "Q-AAGH", "Q-AAJGH"])
def test_run_batch_matches_sequential(tpch_db, template):
    """All-miss batches: run_batch == sequential across every template."""
    qs = _template_batches(tpch_db, (0.95, 0.9, 0.85, 0.8))[template]
    qs = qs + [qs[0], qs[-1]]  # duplicates -> within-batch deferral waves
    e_seq, e_bat = _engines(tpch_db)
    seq = [e_seq.run(q) for q in qs]
    bat = e_bat.run_batch(qs)
    _assert_run_parity(seq, bat, template)
    _assert_index_parity(e_seq, e_bat, template)
    assert sum(1 for _, i in bat if i.created) >= 1
    # At least the duplicate of the most selective (created) query hits.
    assert sum(1 for _, i in bat if i.reused) >= 1


def test_run_batch_mixed_hits_and_misses(tpch_db):
    """Pre-warmed sketches serve from the probe phase; the rest admit."""
    batches = _template_batches(tpch_db, (0.95, 0.85))
    warm = [batches["Q-AGH"][0], batches["Q-AJGH"][0]]
    cold = [batches["Q-AGH"][1], batches["Q-AJGH"][1], batches["Q-AAGH"][0]]
    e_seq, e_bat = _engines(tpch_db)
    for q in warm:
        e_seq.run(q)
        e_bat.run(q)
    mixed = [warm[0], cold[0], warm[1], cold[1], cold[2], warm[0]]
    seq = [e_seq.run(q) for q in mixed]
    bat = e_bat.run_batch(mixed)
    _assert_run_parity(seq, bat, "mixed")
    _assert_index_parity(e_seq, e_bat, "mixed")
    assert any(i.reused for _, i in bat) and any(i.created for _, i in bat)


def test_run_batch_mixed_signature_groups_one_wave(tpch_db):
    """A batch spanning several signature groups (different templates and
    aggregates) shares per-group products without cross-talk."""
    batches = _template_batches(tpch_db, (0.9, 0.8))
    other_agg = Query("lineitem", ("l_suppkey",), Aggregate("sum", "l_extendedprice"))
    other_agg = dataclasses.replace(
        other_agg, having=Having(">", _threshold(other_agg, tpch_db, 0.9)))
    qs = (batches["Q-AGH"] + batches["Q-AJGH"] + batches["Q-AAGH"]
          + batches["Q-AAJGH"] + [other_agg])
    e_seq, e_bat = _engines(tpch_db)
    seq = [e_seq.run(q) for q in qs]
    bat = e_bat.run_batch(qs)
    _assert_run_parity(seq, bat, "multi-group")
    _assert_index_parity(e_seq, e_bat, "multi-group")


def test_run_batch_interleaved_mutations():
    """batch -> append -> batch (repairs) -> delete -> batch, bit-for-bit."""
    db = Database({"crimes": make_crimes(20_000, seed=11)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    taus = np.quantile(sums, np.linspace(0.95, 0.7, 6))
    qs = [dataclasses.replace(base, having=Having(">", float(t))) for t in taus]
    e_seq, e_bat = _engines(db)

    _assert_run_parity([e_seq.run(q) for q in qs], e_bat.run_batch(qs), "cold")

    fresh = make_crimes(2_500, seed=99)
    for e in (e_seq, e_bat):
        e.append_rows("crimes", {a: np.asarray(fresh[a]) for a in fresh.schema})
    seq2 = [e_seq.run(q) for q in qs]
    bat2 = e_bat.run_batch(qs)
    _assert_run_parity(seq2, bat2, "post-append")
    assert all(i.reused and i.repaired for _, i in bat2)

    for e in (e_seq, e_bat):
        e.delete_rows("crimes", np.asarray(e.db["crimes"]["year"]) < 2012)
    _assert_run_parity([e_seq.run(q) for q in qs], e_bat.run_batch(qs),
                       "post-delete")
    _assert_index_parity(e_seq, e_bat, "post-mutations")


@pytest.mark.parametrize("strategy", ["NO-PS", "RAND-GB", "CB-OPT-REL"])
def test_run_batch_other_strategies(tpch_db, strategy):
    qs = _template_batches(tpch_db, (0.95, 0.85))["Q-AGH"]
    qs = qs + [qs[0]]
    e_seq, e_bat = _engines(tpch_db, strategy=strategy)
    seq = [e_seq.run(q) for q in qs]
    bat = e_bat.run_batch(qs)
    _assert_run_parity(seq, bat, strategy)
    _assert_index_parity(e_seq, e_bat, strategy)


def test_run_batch_clustered_engine(tpch_db):
    """cluster_tables=True: the first admission re-clusters the table; batch
    and sequential agree because selection is GB-fast-path (group-pinned
    incidence) and the aggregates are integral."""
    qs = _template_batches(tpch_db, (0.95, 0.9, 0.8))["Q-AGH"]
    e_seq, e_bat = _engines(tpch_db, cluster_tables=True)
    seq = [e_seq.run(q) for q in qs]
    bat = e_bat.run_batch(qs)
    _assert_run_parity(seq, bat, "clustered")
    _assert_index_parity(e_seq, e_bat, "clustered")
    assert e_bat.db["lineitem"].layout is not None


def test_shared_miss_path_work(tpch_db):
    """The whole point: a B-query miss batch pays one sample, one AQR pass,
    one group encoding and one WHERE/agg scan per signature group."""
    qs = _template_batches(tpch_db, (0.97, 0.95, 0.92, 0.9))["Q-AGH"]
    # Q-AGH has a single group-by candidate: disable the single-candidate
    # shortcut so the batch actually exercises the shared sample/AQR pass
    # this test pins.
    eng = PBDSEngine(tpch_db, strategy="CB-OPT-GB", n_ranges=40, theta=0.1,
                     seed=0, min_selectivity_gain=0.98,
                     selection=SelectionConfig(skip_single_candidate=False))
    out = eng.run_batch(qs)
    n_created = sum(1 for _, i in out if i.created)
    assert n_created >= 2
    assert eng.samples.misses == 1 and eng.aqr.misses == 1
    # One full-table group encoding for the fact table's group-by; each
    # created sketch's instance adds one (distinct instance objects).
    s = eng.catalog.stats
    assert s["encode_groups"] <= 1 + n_created
    # Instances materialize once per created sketch — the shared inner block
    # never re-materializes, and capture never scans per query.
    assert s["instance_build"] == n_created


def test_steady_state_reuse_zero_recompiles(tpch_db):
    """After warmup, reuse over instances padded to their row class compiles
    nothing new — even after a small mutation + repair shifts every
    instance's row count."""
    qs = _template_batches(tpch_db, (0.97, 0.94))["Q-AGH"]
    eng = PBDSEngine(tpch_db, strategy="CB-OPT-GB", n_ranges=40, theta=0.1,
                     seed=0, min_selectivity_gain=0.98)
    cold = eng.run_batch(qs)   # admit + warm the reuse path
    created = [i for i, (_, inf) in enumerate(cold) if inf.created]
    assert created
    eng.run_batch(qs)   # first reuse pass flushes any remaining warmup
    with count_xla_compiles() as events:
        out = eng.run_batch(qs)
    assert all(out[i][1].reused for i in created)
    assert len(events) == 0, f"steady-state reuse compiled {len(events)} programs"

    # A small append shifts the logical instance sizes; padding to the row
    # class keeps the physical shapes in the same compiled size class.
    fact = eng.db["lineitem"]
    batch = {a: np.asarray(fact[a])[:64] for a in fact.schema}
    eng.append_rows("lineitem", batch)
    eng.run_batch(qs)  # repair + rebuild instances (delta-sized, may compile
    #                    batch-shaped delta ops once)
    eng.run_batch(qs)
    with count_xla_compiles() as events:
        out = eng.run_batch(qs)
    assert all(out[i][1].reused for i in created)
    assert len(events) == 0, (
        f"post-mutation steady state compiled {len(events)} programs")


def test_capture_sketches_batch_matches_single(tpch_db):
    from repro.core import capture_sketch, equi_depth_ranges, provenance_mask
    from repro.core.sketch import capture_sketches_batch

    qs = _template_batches(tpch_db, (0.95, 0.9, 0.8))["Q-AGH"]
    ranges = equi_depth_ranges(tpch_db["lineitem"], "l_suppkey", 40)
    provs = [provenance_mask(q, tpch_db) for q in qs]
    batched = capture_sketches_batch(qs, tpch_db, [ranges] * len(qs), provs)
    for q, prov, sk_b in zip(qs, provs, batched):
        sk_s = capture_sketch(q, tpch_db, ranges, prov=prov)
        np.testing.assert_array_equal(sk_b.bits, sk_s.bits)
        assert sk_b.size_rows == sk_s.size_rows
        assert sk_b.total_rows == sk_s.total_rows


def test_fragment_bitmap_batch_kernel_parity():
    """Pallas interpret-mode batched kernel == per-mask reference kernel."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import fragment_bitmap_batch_ref

    rng = np.random.default_rng(0)
    n, n_ranges, b = 5_000, 37, 5
    bucket = rng.integers(0, n_ranges, n).astype(np.int32)
    provs = rng.random((b, n)) < 0.05
    import jax.numpy as jnp

    ref_bits = np.asarray(fragment_bitmap_batch_ref(
        jnp.asarray(provs), jnp.asarray(bucket), n_ranges))
    for backend in ("ref", "interpret"):
        got = np.asarray(kops.fragment_bitmap_batch(
            jnp.asarray(provs), jnp.asarray(bucket), n_ranges, backend=backend))
        np.testing.assert_array_equal(got, ref_bits, err_msg=backend)
    # Per-mask single kernel agrees too.
    for i in range(b):
        single = np.asarray(kops.fragment_bitmap(
            jnp.asarray(provs[i]), jnp.asarray(bucket), n_ranges))
        np.testing.assert_array_equal(ref_bits[i], single)


def test_estimate_size_multi_matches_single(tpch_db):
    """The padded (query x candidate) launch returns the same estimates the
    per-query path does (integral est_rows exactly; probabilistic fields to
    float tolerance — padding may reassociate their f32 sums)."""
    from repro.aqp.sampling import stratified_reservoir_sample
    from repro.aqp.size_estimation import (
        EstimationSpec,
        approximate_query_result,
        estimate_size_batched,
        estimate_size_multi,
    )
    from repro.core import equi_depth_ranges

    qs = _template_batches(tpch_db, (0.9, 0.8))["Q-AGH"]
    key = jax.random.PRNGKey(0)
    samples = stratified_reservoir_sample(
        key, tpch_db["lineitem"], qs[0].groupby, 0.1)
    cands = ["l_suppkey", "l_partkey", "l_quantity"]
    # Different n_ranges per query exercises the pow2 fragment-axis padding.
    specs = []
    for q, nr in zip(qs, (40, 56)):
        ranges_by = {a: equi_depth_ranges(tpch_db["lineitem"], a, nr) for a in cands}
        specs.append(EstimationSpec(
            q=q, samples=samples, ranges_by_attr=ranges_by,
            aqr=approximate_query_result(key, q, tpch_db, samples)))
    multi = estimate_size_multi(tpch_db, specs)
    for spec, got in zip(specs, multi):
        ref = estimate_size_batched(
            key, spec.q, tpch_db, spec.ranges_by_attr, spec.samples,
            aqr=spec.aqr)
        for a in cands:
            np.testing.assert_array_equal(got[a].est_bits, ref[a].est_bits)
            assert got[a].est_rows == ref[a].est_rows  # exact integral f32
            assert got[a].expected_rows == pytest.approx(
                ref[a].expected_rows, rel=1e-4)
            assert got[a].lo_rows == pytest.approx(ref[a].lo_rows, rel=1e-4)
            assert got[a].hi_rows == pytest.approx(ref[a].hi_rows, rel=1e-4)
