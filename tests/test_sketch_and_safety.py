"""Sketch capture / application / safety / index-reuse behaviour."""
import numpy as np
import pytest

from repro.core import (
    Aggregate, Database, Having, Predicate, Query, SketchIndex, apply_sketch,
    capture_sketch, equi_depth_ranges, execute, execute_with_sketch,
    is_safe_sketch, prefilter_candidates, safe_attributes, subsumes,
)
from repro.core.datasets import make_crimes
from repro.runtime.shapes import row_class


@pytest.fixture(scope="module")
def db():
    return Database({"crimes": make_crimes(20_000, seed=3)})


@pytest.fixture(scope="module")
def q():
    # Threshold at the ~85th percentile of group sums so the sketch actually
    # skips fragments on a 20k-row table.
    return Query(
        table="crimes",
        groupby=("district", "year"),
        agg=Aggregate("sum", "records"),
        having=Having(">", 400.0),
    )


@pytest.mark.parametrize("attr", ["district", "year", "month", "records", "beat"])
def test_sketch_is_safe_on_any_attr(db, q, attr):
    """SUM >= 0 with HAVING '>' is upward monotone: all attrs safe (Sec. 4.3)."""
    ranges = equi_depth_ranges(db["crimes"], attr, 50)
    sk = capture_sketch(q, db, ranges)
    assert is_safe_sketch(q, db, sk)
    assert 0.0 < sk.selectivity <= 1.0


def test_sketch_covers_provenance(db, q):
    from repro.core import provenance_mask, sketch_keep_mask

    ranges = equi_depth_ranges(db["crimes"], "beat", 50)
    sk = capture_sketch(q, db, ranges)
    prov = provenance_mask(q, db)
    keep = np.asarray(sketch_keep_mask(sk, db["crimes"]))
    assert (keep | ~prov).all()  # every provenance row kept


def test_avg_having_restricts_safety(db):
    q_avg = Query("crimes", ("district",), Aggregate("avg", "records"), having=Having(">", 5.0))
    safe = safe_attributes(q_avg, db)
    assert set(safe) == {"district"}  # only GB attrs safe for AVG


def test_prefilter_keeps_gb_attrs(db, q):
    cands = prefilter_candidates(q, db, ("district", "year", "month", "beat"), 100)
    assert "district" in cands and "year" in cands  # GB attrs exempt
    assert "month" not in cands  # 12 distinct < 100 ranges, not a GB attr
    assert "beat" in cands  # enough distinct values


def test_index_reuse_subsumption(db, q):
    idx = SketchIndex()
    sk = capture_sketch(q, db, equi_depth_ranges(db["crimes"], "district", 25))
    idx.insert(q, sk)
    # Higher threshold => subset provenance => reusable.
    import dataclasses

    q_higher = dataclasses.replace(q, having=Having(">", q.having.value + 200.0))
    assert subsumes(q, q_higher)
    assert idx.lookup(q_higher) is not None
    # Lower threshold needs MORE data: not reusable.
    q_lower = dataclasses.replace(q, having=Having(">", q.having.value - 300.0))
    assert not subsumes(q, q_lower)
    assert idx.lookup(q_lower) is None
    # Different group-by: not reusable.
    q_other = dataclasses.replace(q, groupby=("month",))
    assert idx.lookup(q_other) is None
    # Reused sketch still yields exact results.
    res = execute_with_sketch(q_higher, db, idx.lookup(q_higher))
    assert res.canonical() == execute(q_higher, db).canonical()


def test_apply_sketch_shrinks_db(db):
    # A 99th-percentile threshold leaves a handful of groups => the sketch
    # must actually skip fragments.
    import dataclasses

    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    sums = execute(base, db).values
    qs = dataclasses.replace(base, having=Having(">", float(np.quantile(sums, 0.99))))
    sk = capture_sketch(qs, db, equi_depth_ranges(db["crimes"], "beat", 50))
    db2 = apply_sketch(sk, db)
    # Instances are padded to their row class (masked tail) so reuse
    # execution hits an already-compiled shape: logical rows == size_rows,
    # physical rows are the row class of that count.
    from repro.core.table import PAD_VALID

    inst = db2["crimes"]
    assert int(np.asarray(inst[PAD_VALID]).sum()) == sk.size_rows
    assert inst.num_rows == row_class(sk.size_rows)
    assert inst.num_rows < db["crimes"].num_rows


# -- low-cardinality group-by attributes (satellite regression) ----------------
# GB attrs are exempt from the distinct-count pre-filter, so an attribute with
# fewer distinct values than n_ranges reaches ``equi_depth_ranges``: the
# deduplicated bounds collapse to a few fat, value-aligned fragments.  Every
# path downstream (capture, application, estimation, maintenance, the engine)
# must handle the degenerate partition.

def _lowcard_db(n=6_000, n_distinct=3, seed=11):
    from repro.core.table import from_numpy

    rng = np.random.default_rng(seed)
    return Database({"t": from_numpy("t", {
        "g": rng.integers(0, n_distinct, n).astype(np.float32),
        "v": rng.random(n).astype(np.float32),
    })})


def _lowcard_q(tau=600.0):
    return Query("t", ("g",), Aggregate("count", None), having=Having(">", tau))


def test_lowcard_gb_ranges_dedupe_and_value_align():
    db2 = _lowcard_db()
    ranges = equi_depth_ranges(db2["t"], "g", 10)
    # 3 distinct values -> at most 2 interior bounds survive dedupe.
    assert ranges.n_ranges <= 3 + 1
    assert np.all(np.diff(ranges.bounds) > 0)  # strictly increasing
    # Value-aligned: every row of one group value lands in one fragment.
    col = np.asarray(db2["t"]["g"])
    frag = np.asarray(ranges.bucketize(col))
    for v in np.unique(col):
        assert len(np.unique(frag[col == v])) == 1


def test_lowcard_gb_capture_apply_execute():
    db2 = _lowcard_db()
    q2 = _lowcard_q(tau=2100.0)  # ~one of three groups passes
    ranges = equi_depth_ranges(db2["t"], "g", 10)
    sk = capture_sketch(q2, db2, ranges)
    assert is_safe_sketch(q2, db2, sk)
    res = execute_with_sketch(q2, db2, sk)
    assert res.canonical() == execute(q2, db2).canonical()
    # The fat-fragment partition still skips: non-passing groups' fragments
    # are not covered when the threshold splits the groups.
    if 0 < int(np.asarray(sk.bits).sum()) < sk.ranges.n_ranges:
        assert sk.selectivity < 1.0


def test_lowcard_gb_estimate_path():
    """The padded estimator accepts a candidate whose deduped n_ranges is far
    below the requested count (ragged fragment axis)."""
    import jax

    from repro.aqp.sampling import SampleCache
    from repro.aqp.size_estimation import EstimationConfig, estimate_size_batched

    db2 = _lowcard_db()
    q2 = _lowcard_q(tau=2100.0)
    key = jax.random.PRNGKey(0)
    samples = SampleCache().get_or_create(key, db2["t"], ("g",), 0.2)
    ranges = equi_depth_ranges(db2["t"], "g", 10)
    ests = estimate_size_batched(key, q2, db2, {"g": ranges}, samples,
                                 EstimationConfig())
    est = ests["g"]
    assert est.est_bits.shape[0] == ranges.n_ranges
    assert 0.0 <= est.est_selectivity <= 1.0


def test_lowcard_gb_engine_end_to_end_with_maintenance():
    """Engine admission + repeat hit + append/repair over the degenerate
    partition: results stay exact throughout."""
    from repro.core.engine import PBDSEngine

    db2 = _lowcard_db()
    q2 = _lowcard_q(tau=1000.0)
    eng = PBDSEngine(db2, strategy="CB-OPT-GB", n_ranges=10, theta=0.2, seed=0,
                     min_selectivity_gain=2.0)
    res, info = eng.run(q2)
    assert info.created
    assert res.canonical() == execute(q2, db2).canonical()
    _, info2 = eng.run(q2)
    assert info2.reused
    # Mutate: append rows biased into one group, then re-run -> repair path.
    fact = eng.db["t"]
    batch = {"g": np.full(500, 1.0, np.float32),
             "v": np.linspace(0, 1, 500, dtype=np.float32)}
    eng.append_rows("t", batch)
    res3, info3 = eng.run(q2)
    assert info3.reused and info3.repaired
    assert res3.canonical() == execute(q2, eng.db).canonical()
