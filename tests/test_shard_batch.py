"""Fused SPMD sharded serving + ShardedEngine.run_batch differential suite.

Contracts under test (the acceptance criteria of the SPMD serving PR):

  * fused stacked one-launch serving is *bit-identical* to the per-shard
    host loop and to single-node execution across all four templates,
    including under interleaved appends/deletes;
  * ``ShardedEngine.run_batch(qs)`` is semantically equivalent to
    ``[se.run(q) for q in qs]`` — results, index contents, sketch bits,
    per-shard maintainer state and watermarks;
  * the warm hit path costs exactly ONE fused XLA launch per batch
    (counter-asserted), regardless of how many queries or entries hit;
  * the stacked layout is pow2-quantized on the shard-row, group and query
    axes, so shard-count or registered-sketch-set changes within a padded
    bucket compile nothing new;
  * shard-side registrations evict with the coordinator's recency clock
    (``ShardedEngine.prune`` / ``max_registered``), bounding per-shard
    maintainer + instance memory.
"""
import contextlib
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    Aggregate,
    Database,
    Having,
    JoinSpec,
    Predicate,
    Query,
    ShardedEngine,
    execute,
)
from repro.core import shard as shard_mod
from repro.core.datasets import make_crimes, make_tpch
from repro.runtime.guards import retrace_guard

N_ROWS = 20_000


@contextlib.contextmanager
def count_xla_compiles():
    """Count real backend compilations via the shared retrace guard
    (cached executions emit no event)."""
    with retrace_guard(allowed=None) as watch:
        yield watch.events


def _threshold(q, db, quantile):
    vals = execute(dataclasses.replace(q, having=None, outer_having=None), db).values
    return float(np.quantile(vals, quantile))


def _tpch_template_batches(db, quantiles=(0.55, 0.8, 0.9)):
    """Per template, a batch of queries differing only in HAVING thresholds
    (ascending, so later members hit the first member's sketch)."""
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
    aagh = Query(
        "lineitem", ("l_partkey", "l_suppkey"), Aggregate("sum", "l_quantity"),
        having=Having(">", 0.0),
        outer_groupby=("l_suppkey",), outer_agg=Aggregate("sum", None),
    )
    batches["Q-AAGH"] = [
        dataclasses.replace(
            aagh, outer_having=Having(">", _threshold(aagh, db, qt)))
        for qt in quantiles
    ]
    aajgh = Query(
        "lineitem", ("l_partkey", "l_suppkey"), Aggregate("count", None),
        join=JoinSpec("orders", "l_orderkey", "o_orderkey"),
        having=Having(">", 0.0),
        outer_groupby=("l_suppkey",), outer_agg=Aggregate("sum", None),
    )
    batches["Q-AAJGH"] = [
        dataclasses.replace(
            aajgh, outer_having=Having(">", _threshold(aajgh, db, qt)))
        for qt in quantiles
    ]
    return batches


def _crimes_engines(db, n_shards, **kw):
    args = dict(n_ranges=25, theta=0.1, seed=0, min_selectivity_gain=2.0)
    args.update(kw)
    return (ShardedEngine(db, "crimes", "district", n_shards=n_shards, **args),
            ShardedEngine(db, "crimes", "district", n_shards=n_shards, **args))


def _snapshot(se):
    """Comparable engine state: index sketches, shard maintainer bits,
    registration count, watermark."""
    index = sorted(
        (repr(e.query.signature()), e.sketch.bits.tobytes(),
         e.sketch.size_rows)
        for e in se.engine.index.entries())
    shard_bits = [
        sorted(m.bits().tobytes() for m in shard.maintainers.values())
        for shard in se.shards
    ]
    return {
        "index": index,
        "shard_bits": shard_bits,
        "n_registered": len(se._registered),
        "watermark": se.min_watermark(),
        "version": se.version,
    }


def _assert_outs_equal(outs_b, outs_s, ctx=""):
    assert len(outs_b) == len(outs_s)
    for i, ((rb, ib), (rs, is_)) in enumerate(zip(outs_b, outs_s)):
        assert rb.canonical() == rs.canonical(), f"{ctx}[{i}]"
        assert ib.reused == is_.reused, f"{ctx}[{i}]"
        assert ib.created == is_.created, f"{ctx}[{i}]"


def test_run_batch_matches_sequential_all_templates():
    db = make_tpch(N_ROWS, seed=7)
    for name, batch in _tpch_template_batches(db).items():
        se_b = ShardedEngine(db, "lineitem", "l_suppkey", n_shards=2,
                             n_ranges=32, theta=0.1, seed=0,
                             min_selectivity_gain=2.0)
        se_s = ShardedEngine(db, "lineitem", "l_suppkey", n_shards=2,
                             n_ranges=32, theta=0.1, seed=0,
                             min_selectivity_gain=2.0)
        outs_b = se_b.run_batch(batch)
        outs_s = [se_s.run(q) for q in batch]
        _assert_outs_equal(outs_b, outs_s, name)
        assert _snapshot(se_b) == _snapshot(se_s), name
        # Warm pass: every member is a routed hit now.
        outs_b2 = se_b.run_batch(batch)
        outs_s2 = [se_s.run(q) for q in batch]
        _assert_outs_equal(outs_b2, outs_s2, name + ":warm")
        assert all(ib.reused for _, ib in outs_b2), name
        for (rb, _), q in zip(outs_b2, batch):
            assert rb.canonical() == execute(q, se_b.db).canonical(), name


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
def test_run_batch_mixed_hits_and_misses(n_shards):
    db = Database({"crimes": make_crimes(N_ROWS, seed=3)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    qs = [dataclasses.replace(base, having=Having(">", float(np.quantile(sums, qt))))
          for qt in (0.5, 0.7, 0.9)]
    base2 = Query("crimes", ("district",), Aggregate("count", None))
    counts = execute(base2, db).values
    q2 = dataclasses.replace(base2, having=Having(">", float(np.quantile(counts, 0.6))))

    se_b, se_s = _crimes_engines(db, n_shards)
    # Warm one entry so the batch mixes hits with misses (plus a duplicate
    # and an ascending pair that defers a wave).
    se_b.run(qs[0])
    se_s.run(qs[0])
    batch = [qs[1], qs[0], q2, qs[2], qs[1]]
    outs_b = se_b.run_batch(batch)
    outs_s = [se_s.run(q) for q in batch]
    _assert_outs_equal(outs_b, outs_s, f"S={n_shards}")
    assert _snapshot(se_b) == _snapshot(se_s)
    for (rb, _), q in zip(outs_b, batch):
        assert rb.canonical() == execute(q, se_b.db).canonical()


def test_run_batch_interleaved_mutations_and_maintainer_state():
    rng = np.random.default_rng(19)
    db = Database({"crimes": make_crimes(N_ROWS, seed=9)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    queries = [
        dataclasses.replace(base, having=Having(">", float(np.quantile(sums, qt))))
        for qt in (0.6, 0.8)
    ]
    # A non-group-local query too: groups span shards, the fused path must
    # serve from the coordinator-maintained bits.
    byear = Query("crimes", ("year",), Aggregate("sum", "records"))
    ysums = execute(byear, db).values
    queries.append(dataclasses.replace(
        byear, having=Having(">", float(np.quantile(ysums, 0.7)))))

    se_b, se_s = _crimes_engines(db, 4)
    se_b.run_batch(queries)
    for q in queries:
        se_s.run(q)
    assert _snapshot(se_b) == _snapshot(se_s)

    n_batches = 0
    for step in range(16):
        op = rng.choice(["append", "delete", "batch"], p=[0.3, 0.25, 0.45])
        if op == "append":
            batch_rows = make_crimes(int(rng.integers(200, 600)),
                                     seed=int(rng.integers(1 << 30)))
            rows = {a: np.asarray(batch_rows[a]) for a in batch_rows.schema}
            se_b.append_rows("crimes", rows)
            se_s.append_rows("crimes", rows)
        elif op == "delete":
            mask = rng.random(se_b.db["crimes"].num_rows) < 0.02
            se_b.delete_rows("crimes", mask)
            se_s.delete_rows("crimes", mask)
        else:
            picks = [queries[int(rng.integers(len(queries)))]
                     for _ in range(int(rng.integers(2, 5)))]
            outs_b = se_b.run_batch(picks)
            outs_s = [se_s.run(q) for q in picks]
            _assert_outs_equal(outs_b, outs_s, f"step{step}")
            for (rb, ib), q in zip(outs_b, picks):
                assert ib.reused, step
                assert rb.canonical() == execute(q, se_b.db).canonical(), step
            # Watermark gate drained every shard before serving.
            assert se_b.min_watermark() == se_b.version
            assert _snapshot(se_b) == _snapshot(se_s), step
            n_batches += 1
    assert n_batches >= 3


def test_fused_equals_host_loop_bitwise():
    """Same engine, both serving paths: values must match bit-for-bit
    (not just canonically) — the stacked merge reproduces the host-loop
    float32 arithmetic exactly inside the integral envelope."""
    db = Database({"crimes": make_crimes(N_ROWS, seed=5)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    q = dataclasses.replace(base, having=Having(">", float(np.quantile(sums, 0.8))))
    qavg = dataclasses.replace(
        base, agg=Aggregate("avg", "records"),
        having=Having(">", float(np.quantile(
            execute(dataclasses.replace(base, agg=Aggregate("avg", "records")),
                    db).values, 0.8))))
    se, _ = _crimes_engines(db, 4)
    for query in (q, qavg):
        se.run(query)
        se.fused = True
        rf, inf_f = se.run(query)
        assert se.last_route.fused and se.last_route.t_launch_s >= 0
        se.fused = False
        rl, inf_l = se.run(query)
        assert not se.last_route.fused
        se.fused = True
        assert inf_f.shards_contacted == inf_l.shards_contacted
        assert inf_f.shards_skipped == inf_l.shards_skipped
        assert sorted(rf.group_values) == sorted(rl.group_values)
        assert np.array_equal(rf.values, rl.values)
        for a in rf.group_values:
            assert np.array_equal(rf.group_values[a], rl.group_values[a])
        single = execute(query, se.db)
        assert rf.canonical() == single.canonical()


def test_hit_batch_costs_one_fused_launch():
    db = Database({"crimes": make_crimes(N_ROWS, seed=11)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    qs = [dataclasses.replace(base, having=Having(">", float(np.quantile(sums, qt))))
          for qt in (0.6, 0.85)]
    base2 = Query("crimes", ("district",), Aggregate("count", None))
    counts = execute(base2, db).values
    q2 = dataclasses.replace(base2, having=Having(">", float(np.quantile(counts, 0.6))))
    se, _ = _crimes_engines(db, 4)
    batch = qs + [q2, qs[0], qs[1]]
    se.run_batch(batch)  # cold: admits + registers
    se.run_batch(batch)  # warms the stacked arrays + compiled shapes
    before = shard_mod.LAUNCH_COUNTS["fused_partials"]
    outs = se.run_batch(batch)  # 5 queries, 3 distinct entries
    assert shard_mod.LAUNCH_COUNTS["fused_partials"] - before == 1
    assert all(ib.reused for _, ib in outs)
    assert se.last_route.fused and se.last_route.n_queries == len(batch)
    # Single-query hits also cost exactly one launch.
    before = shard_mod.LAUNCH_COUNTS["fused_partials"]
    se.run(qs[0])
    assert shard_mod.LAUNCH_COUNTS["fused_partials"] - before == 1


def test_stacked_pow2_quantization_avoids_recompiles():
    """Shard-count and registered-sketch-set changes inside one padded
    bucket (shard-row, group AND query axes pow2-quantized) must compile
    nothing new — mirrors the ``sizes_mat`` test in ``test_catalog.py``."""
    db = Database({"crimes": make_crimes(N_ROWS, seed=13)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    # Low thresholds: the sketch covers (almost) all fragments, so every
    # shard is contacted and the stacked shard axis tracks the shard count.
    q3 = dataclasses.replace(base, having=Having(">", float(np.quantile(sums, 0.1))))
    q4 = dataclasses.replace(base, having=Having(">", float(np.quantile(sums, 0.15))))

    se3, _ = _crimes_engines(db, 3)
    se3.run(q3)
    se3.run(q3)  # warm: compiles the fused size class (s_pad=4, r_pad, g_pad)
    trace_before = shard_mod.TRACE_COUNTS["fused_partials"]

    # 4 shards: s_pad is still 4, per-shard rows shrink within the same
    # pow2 row bucket (20k rows: ceil to 8192 at both 3 and 4 shards), and a
    # second registered sketch with the same group-by lands in the same
    # (r_pad, g_pad) bucket — the fused launch must never retrace for any of
    # them (the stacked *build* may compile one-time gather shapes; the
    # serving launch itself is pinned by the trace counter).
    se4, _ = _crimes_engines(db, 4)
    se4.run(q3)  # cold: capture + registration
    se4.run(q3)  # first fused serve: builds the stack
    se4.run(q4)
    se4.run(q4)
    assert shard_mod.TRACE_COUNTS["fused_partials"] == trace_before, (
        "fused launch retraced inside one pow2 bucket")

    # Steady state: repeated fused serves over both sketches (and a mixed
    # hit batch through the query-axis path, once warmed) compile nothing.
    se4.run_batch([q3, q4])
    with count_xla_compiles() as events:
        se4.run(q3)
        assert se4.last_route.fused
        se4.run(q4)
        se4.run_batch([q3, q4, q3])
    assert len(events) == 0, (
        f"steady-state fused serving compiled {len(events)} programs")
    assert shard_mod.TRACE_COUNTS["fused_partials"] == trace_before


def test_prune_bounds_shard_registrations():
    """Shard-side ``SketchIndex.prune`` wiring: registrations evict with the
    coordinator's recency clock and per-shard state stays bounded."""
    db = Database({"crimes": make_crimes(N_ROWS, seed=17)})
    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    years = np.asarray(db["crimes"]["year"])
    lo, hi = int(years.min()), int(years.max())
    qs = []
    for k, yr in enumerate((lo, lo + 1, lo + 2)):
        b = dataclasses.replace(base, where=Predicate("year", ">=", float(yr)))
        sums = execute(b, db).values
        qs.append(dataclasses.replace(
            b, having=Having(">", float(np.quantile(sums, 0.8)))))
    # Distinct WHERE predicates => distinct index entries (no subsumption).
    se = ShardedEngine(db, "crimes", "district", n_shards=3, n_ranges=25,
                       theta=0.1, seed=0, min_selectivity_gain=2.0,
                       max_registered=2)
    for q in qs:
        se.run(q)
        se.run(q)
    assert len(se.engine.index) == 2
    assert len(se._registered) == 2
    for shard in se.shards:
        assert len(shard.maintainers) <= 2
        assert len(shard._inst) <= 2
    assert len(se.engine.catalog._stacked) <= 2
    # The least-recently-hit sketch (qs[0]) was evicted: next run re-captures.
    _, info = se.run(qs[0])
    assert info.created and not info.reused
    res, info2 = se.run(qs[0])
    assert info2.reused
    assert res.canonical() == execute(qs[0], se.db).canonical()
    # Manual prune to 1 drops shard state for the evicted entries too.
    assert se.prune(1) >= 1
    assert len(se._registered) == 1
    for shard in se.shards:
        assert len(shard.maintainers) <= 1


def test_spmd_mesh_shard_map_path():
    """With a real multi-device mesh (forced host devices), the fused path
    runs through shard_map + psum and stays exact."""
    code = textwrap.dedent("""
        import dataclasses
        import numpy as np
        from repro.core import (Aggregate, Database, Having, Query,
                                ShardedEngine, execute)
        from repro.core import shard as shard_mod
        from repro.core.datasets import make_crimes

        db = Database({"crimes": make_crimes(6_000, seed=3)})
        base = Query("crimes", ("district",), Aggregate("sum", "records"))
        sums = execute(base, db).values
        q = dataclasses.replace(
            base, having=Having(">", float(np.quantile(sums, 0.1))))
        se = ShardedEngine(db, "crimes", "district", n_shards=4, n_ranges=16,
                           theta=0.1, seed=0, min_selectivity_gain=2.0)
        assert se._mesh is not None and se._mesh.devices.size == 4
        se.run(q)
        res, info = se.run(q)
        assert info.reused and se.last_route.fused
        assert shard_mod._SPMD_FNS, "shard_map path was not taken"
        assert res.canonical() == execute(q, se.db).canonical()
        print("SPMD_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD_OK" in proc.stdout
