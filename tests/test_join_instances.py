"""Joined sketch instances gathered from the cached join layout.

A join query over a sketch instance joins the instance with the right table.
Where the base table's layout is cached, ``Catalog.join`` gathers the joined
instance from it instead of joining from scratch, and registers it as a
subset of the layout so that its group encodings and WHERE masks derive by a
gather.  These tests pin the gathered instance to the from-scratch join,
padding and unmatched fact rows included: the same columns bit for bit, the
same fact-row back-map, the same group encoding and the same sums and
counts."""
import numpy as np
import pytest

from repro.core import Aggregate, Catalog, Database, Having, JoinSpec, PBDSEngine, Query
from repro.core.queries import Predicate, execute, inner_group_partials
from repro.core.sketch import _pad_instance_pow2
from repro.core.table import PAD_VALID, from_numpy

N_FACT, N_DIM = 3_000, 400


@pytest.fixture(scope="module")
def db():
    """A fact table whose foreign key misses the dimension for about a fifth
    of its rows, on sparse dimension keys; amounts are larger on the first
    ten days, so a sketch on ``f_day`` pays."""
    rng = np.random.default_rng(16)
    dim_key = (np.arange(N_DIM) * 7 + 3).astype(np.int32)
    fk = rng.integers(0, N_DIM * 7 * 5 // 4, N_FACT)
    fk = np.where(rng.random(N_FACT) < 0.8, dim_key[fk % N_DIM], fk).astype(np.int32)
    day = rng.integers(1, 40, N_FACT)
    fact = from_numpy("fact", {
        "f_key": fk,
        "f_day": day.astype(np.int32),
        "f_amount": (rng.random(N_FACT) * np.where(day <= 10, 5000, 1000)).astype(np.float32),
    })
    dim = from_numpy("dim", {
        "d_key": dim_key,
        "d_region": rng.integers(1, 9, N_DIM).astype(np.int32),
        "d_weight": rng.random(N_DIM).astype(np.float32),
    })
    return Database({"fact": fact, "dim": dim})


def _instance(db, catalog, n_rows):
    """A sketch instance of ``n_rows`` fact rows, pow2-padded and registered
    as the engine registers one."""
    fact = db["fact"]
    rows = np.sort(np.random.default_rng(n_rows).choice(fact.num_rows, n_rows, replace=False))
    inst, rows = _pad_instance_pow2(fact.gather(rows), rows, catalog)
    catalog.put_instance(object(), fact, inst, rows=rows)
    return inst


def _joined(db, n_rows, layout_cached):
    cat = Catalog()
    if layout_cached:
        cat.join(db["fact"], db["dim"], "f_key", "d_key")
    inst = _instance(db, cat, n_rows)
    joined, fact_idx = cat.join(inst, db["dim"], "f_key", "d_key")
    return cat, inst, joined, fact_idx


# 1,024 rows need no padding; the others pad to 1,024 and 4,096.
SIZES = [1_024, 700, 2_500]


@pytest.mark.parametrize("n_rows", SIZES)
def test_gathered_join_equals_the_join_from_scratch(db, n_rows):
    cat_g, inst, gathered, idx_g = _joined(db, n_rows, layout_cached=True)
    cat_s, _, scratch, idx_s = _joined(db, n_rows, layout_cached=False)
    assert cat_g.stats["join_instance"] == 1 and cat_g.stats["join_materialize"] == 1
    assert cat_s.stats["join_instance"] == 0 and cat_s.stats["join_materialize"] == 1
    assert inst.num_rows == 1 << (n_rows - 1).bit_length() and inst.has(PAD_VALID)
    assert gathered.name == scratch.name and gathered.primary_key == scratch.primary_key
    assert gathered.schema == scratch.schema
    for a in scratch.schema:
        g, s = np.asarray(gathered[a]), np.asarray(scratch[a])
        assert g.dtype == s.dtype and np.array_equal(g, s), a
    assert np.array_equal(idx_g, idx_s)
    # Unmatched fact rows are dropped, padding rows are kept.
    assert 0 < gathered.num_rows < inst.num_rows


@pytest.mark.parametrize("n_rows", SIZES)
@pytest.mark.parametrize("groupby", [("d_region",), ("f_day", "d_region")])
def test_gathered_join_encodes_and_aggregates_as_from_scratch(db, n_rows, groupby):
    q = Query("fact", groupby, Aggregate("sum", "f_amount"),
              join=JoinSpec("dim", "f_key", "d_key"),
              where=Predicate("d_weight", ">", 0.2))
    cat_g, _, gathered, _ = _joined(db, n_rows, layout_cached=True)
    cat_s, _, scratch, _ = _joined(db, n_rows, layout_cached=False)
    before = cat_g.stats["encode_groups"]
    enc_g, where_g, sums_g, counts_g = inner_group_partials(q, gathered, cat_g)
    enc_s, where_s, sums_s, counts_s = inner_group_partials(q, scratch, cat_s)
    # The gathered instance derives from the layout's encoding and mask.
    assert cat_g.stats["encode_groups_instance"] == 1
    assert cat_g.stats["where_mask_instance"] == 1
    assert cat_g.stats["encode_groups"] == before + 1  # the layout's, once
    assert np.array_equal(enc_g.gid, enc_s.gid) and enc_g.n_groups == enc_s.n_groups
    for a in groupby:
        assert np.array_equal(enc_g.group_values[a], enc_s.group_values[a])
    assert np.array_equal(np.asarray(where_g), np.asarray(where_s))
    assert np.array_equal(np.asarray(sums_g), np.asarray(sums_s))
    assert np.array_equal(np.asarray(counts_g), np.asarray(counts_s))


def test_gathered_join_is_a_counted_miss_and_then_a_hit(db):
    cat, inst, joined, _ = _joined(db, 700, layout_cached=True)
    assert "join_instance" in Catalog.MISS_STATS
    misses = cat.misses()
    again, _ = cat.join(inst, db["dim"], "f_key", "d_key")
    assert again is joined and cat.misses() == misses and cat.stats["join_hit"] == 1


def test_engine_hits_use_the_gathered_join(db):
    """A capture caches the layout in its scan and gathers the joined
    instance in its warm-up; the hit then finds everything cached and answers
    bit for bit as the from-scratch join does."""
    base = Query("fact", ("f_day",), Aggregate("sum", "f_amount"),
                 join=JoinSpec("dim", "f_key", "d_key"))
    vals = execute(base, db).values
    q_cap = Query(base.table, base.groupby, base.agg, join=base.join,
                  having=Having(">", float(np.quantile(vals, 0.5))))
    q_hit = Query(base.table, base.groupby, base.agg, join=base.join,
                  having=Having(">", float(np.quantile(vals, 0.75))))
    eng = PBDSEngine(db, strategy="CB-OPT-GB", n_ranges=20, theta=0.1, seed=0,
                     cluster_tables=False)
    _, cap = eng.run(q_cap)
    assert cap.created
    assert eng.catalog.stats["join_materialize"] == 1
    assert eng.catalog.stats["join_instance"] == 1
    assert eng.catalog.stats["encode_groups"] == 1  # the layout's alone
    misses = eng.catalog.misses()
    res, hit = eng.run(q_hit)
    assert hit.reused and eng.catalog.misses() == misses

    # The same instance joined from scratch in a catalog with no layout.
    entry = eng.index.lookup_entry(q_hit)
    inst = eng.catalog.get_instance(entry.sketch, db["fact"])
    cat = Catalog()
    cat.put_instance(entry.sketch, db["fact"], inst,
                     rows=eng.catalog._instance_parent(inst)[1])
    want = execute(q_hit, db.with_table(inst), catalog=cat)
    assert cat.stats["join_materialize"] == 1 and cat.stats["join_instance"] == 0
    assert np.array_equal(res.values, want.values)
    assert np.array_equal(res.group_values["f_day"], want.group_values["f_day"])
    assert res.canonical() == execute(q_hit, db).canonical()
