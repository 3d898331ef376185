"""The ``tpch-sf1`` configuration: its generator keeps each rule of TPC-H
Clause 4.2.3 at 2,000 orders, its foreign keys hold at any row count and its
bytes are pinned; the engine answers the four ``qajgh-hits`` join templates
as the plain reference does, from sketches and from NO-PS scans; and the
cell's CPU rehearsal is correct and reads the join layout's metrics."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench_util import ROOT, rehearse

from benchmarks.chip import datagen, load_module, reference, traffic

CELL = "tpch-sf1-join-hits"
CURRENT = 9298  # 1995-06-17 in days since 1970-01-01


def _config():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs", "tpch-sf1.json")) as f:
        return json.load(f)


def _generate(seed, orders):
    return datagen.generate(_config(), seed, orders / 1_500_000)


@pytest.fixture(scope="module")
def tables():
    gen = _generate(7, 2000)
    return {k: cols for k, (cols, _) in gen.items()}


def _owner(li, o):
    """Index of each lineitem's order."""
    return np.searchsorted(o["o_orderkey"], li["l_orderkey"])


def _schema(li, o):
    assert len(li) == 16 and len(o) == 9 and len(o["o_orderkey"]) == 2000
    assert all(v.dtype in (np.int32, np.float32) for v in list(li.values()) + list(o.values()))


def _orderkeys(li, o):
    i = np.arange(1, 2001)
    assert np.array_equal(o["o_orderkey"], ((i - 1) // 8) * 32 + (i - 1) % 8 + 1)


def _custkey(li, o):
    c = o["o_custkey"]
    assert c.min() >= 1 and c.max() <= 150_000 and not (c % 3 == 0).any()


def _orderdate(li, o):
    d = o["o_orderdate"]
    assert d.min() >= 8035 and d.max() <= 10440  # 1992-01-01 .. 1998-08-02


def _order_codes(li, o):
    assert set(np.unique(o["o_orderpriority"])) == set(range(1, 6))
    assert o["o_clerk"].min() >= 1 and o["o_clerk"].max() <= 1000
    assert (o["o_shippriority"] == 0).all()


def _linenumbers(li, o):
    per = np.bincount(_owner(li, o), minlength=2000)
    assert per.min() >= 1 and per.max() <= 7 and set(np.unique(per)) == set(range(1, 8))
    first = np.cumsum(per) - per
    assert np.array_equal(li["l_linenumber"],
                          np.arange(len(li["l_linenumber"])) - np.repeat(first, per) + 1)


def _part_and_supplier(li, o):
    p, s = li["l_partkey"].astype(np.int64), li["l_suppkey"].astype(np.int64)
    assert p.min() >= 1 and p.max() <= 200_000
    cands = np.stack([(p + i * (10_000 // 4 + (p - 1) // 10_000)) % 10_000 + 1
                      for i in range(4)])
    assert (cands == s).any(axis=0).all()
    assert s.min() >= 1 and s.max() <= 10_000


def _prices(li, o):
    q, p = li["l_quantity"], li["l_partkey"].astype(np.int64)
    assert q.min() == 1 and q.max() == 50 and (q == np.round(q)).all()
    retail = (90000 + (p // 10) % 20001 + 100 * (p % 1000)) / 100.0
    assert np.array_equal(li["l_extendedprice"], np.round(q * retail, 2).astype(np.float32))
    for col, top in (("l_discount", 10), ("l_tax", 8)):
        cents = li[col].astype(np.float64) * 100
        assert np.allclose(cents, np.round(cents), atol=1e-4)
        assert set(np.round(cents).astype(int)) == set(range(top + 1))


def _dates(li, o):
    od = o["o_orderdate"][_owner(li, o)]
    ship = li["l_shipdate"] - od
    commit = li["l_commitdate"] - od
    receipt = li["l_receiptdate"] - li["l_shipdate"]
    assert (ship.min(), ship.max()) == (1, 121)
    assert (commit.min(), commit.max()) == (30, 90)
    assert (receipt.min(), receipt.max()) == (1, 30)


def _flags(li, o):
    received = li["l_receiptdate"] <= CURRENT
    rf = li["l_returnflag"]
    assert set(np.unique(rf[received])) == {ord("R"), ord("A")}
    assert (rf[~received] == ord("N")).all()
    shipped_late = li["l_shipdate"] > CURRENT
    assert np.array_equal(li["l_linestatus"],
                          np.where(shipped_late, ord("O"), ord("F")))


def _lineitem_codes(li, o):
    assert set(np.unique(li["l_shipinstruct"])) == set(range(4))
    assert set(np.unique(li["l_shipmode"])) == set(range(7))
    assert li["l_comment"].min() >= 0 and o["o_comment"].min() >= 0


def _order_totals(li, o):
    owner = _owner(li, o)
    charge = (li["l_extendedprice"].astype(np.float64) * (1 + li["l_tax"].astype(np.float64))
              * (1 - li["l_discount"].astype(np.float64)))
    total = np.bincount(owner, weights=charge, minlength=2000)
    # float32 line values, rounded to cents once per order
    assert np.allclose(o["o_totalprice"], total, rtol=1e-6, atol=0.02)
    is_open = np.bincount(owner, weights=li["l_linestatus"] == ord("O"), minlength=2000)
    lines = np.bincount(owner, minlength=2000)
    want = np.where(is_open == lines, ord("O"), np.where(is_open == 0, ord("F"), ord("P")))
    assert np.array_equal(o["o_orderstatus"], want)
    assert set(np.unique(want)) == {ord("O"), ord("F"), ord("P")}


RULES = [_schema, _orderkeys, _custkey, _orderdate, _order_codes, _linenumbers,
         _part_and_supplier, _prices, _dates, _flags, _lineitem_codes, _order_totals]


@pytest.mark.parametrize("rule", RULES, ids=[r.__name__.strip("_") for r in RULES])
def test_generator_keeps_the_tpch_rule(tables, rule):
    rule(tables["lineitem"], tables["orders"])


@pytest.mark.parametrize("orders", [64, 777, 2000])
def test_every_lineitem_has_its_order(orders):
    gen = load_module(ROOT, "generators", "tpch").generate(
        np.random.default_rng([2**31 + 3, 0]), orders=orders)
    li, o = gen["lineitem"][0], gen["orders"][0]
    assert len(o["o_orderkey"]) == orders and len(np.unique(o["o_orderkey"])) == orders
    assert np.isin(li["l_orderkey"], o["o_orderkey"]).all()
    assert gen["lineitem"][1] == ("l_orderkey", "l_linenumber")
    assert gen["orders"][1] == ("o_orderkey",)


def test_tpch_data_is_pinned():
    """Same draws, same order, same dtypes for one seed."""
    gen = _generate(3, 2000)
    assert list(gen) == ["lineitem", "orders"]
    h = hashlib.sha256()
    for table in sorted(gen):
        cols = gen[table][0]
        for name in sorted(cols):
            h.update(name.encode())
            h.update(str(cols[name].dtype).encode())
            h.update(cols[name].tobytes())
    assert len(gen["lineitem"][0]["l_orderkey"]) == 8053
    assert h.hexdigest() == "9fc5a256ce53de59eddfdb1308723de14f140857404429cdf4bef4bd5833a183"


# ---------------------------------------------------------------------------
# The engine against the plain reference on the cell's templates
# ---------------------------------------------------------------------------

MIX = traffic.load(os.path.join(ROOT, "benchmarks", "chip", "traffic", "qajgh-hits.json"))
TEMPLATES = traffic.templates(MIX)


@pytest.fixture(scope="module")
def answered(tables):
    """Each template at q = 0.5 (capture) and at q = 0.7 and 0.9 (hits), on
    the cell's engine and on its NO-PS twin."""
    from repro.core import Aggregate, Database, Having, JoinSpec, PBDSEngine, Query
    from repro.core.table import from_numpy

    gen = _generate(7, 2000)
    db = Database({k: from_numpy(k, c, pk) for k, (c, pk) in gen.items()})
    exact = [reference.exact_groups(t, tables) for t in TEMPLATES]

    def query(j, q):
        t = TEMPLATES[j]
        thr = float(np.quantile(exact[j].values, q))
        return Query(t.table, t.groupby, Aggregate(t.agg_fn, t.agg_attr),
                     having=Having(">", thr), join=JoinSpec(*t.join)), thr

    out = {}
    for mode, strategy in (("hits", _config()["engine"]["strategy"]), ("nops", "NO-PS")):
        eng = PBDSEngine(db, **{**_config()["engine"], "strategy": strategy})
        rows = []
        for qs in ([0.5], [0.7, 0.9]):
            asked = [(j, *query(j, q)) for q in qs for j in range(len(TEMPLATES))]
            got = eng.run_batch([q for _, q, _ in asked])
            rows += [(j, thr, res, info) for (j, _, thr), (res, info) in zip(asked, got)]
        out[mode] = rows
    return exact, out


@pytest.mark.parametrize("mode", ["hits", "nops"])
@pytest.mark.parametrize("j", range(len(TEMPLATES)), ids=[t.name for t in TEMPLATES])
def test_engine_answers_as_the_reference(answered, mode, j):
    exact, out = answered
    rows = [r for r in out[mode] if r[0] == j]
    assert len(rows) == 3
    for k, (_, thr, res, info) in enumerate(rows):
        if mode == "hits":
            assert info.created if k == 0 else info.reused
        else:
            assert not info.created and not info.reused
        v = reference.compare(TEMPLATES[j].groupby, res.group_values, res.values,
                              exact[j], exact[j].index(), ">", thr,
                              MIX["checks"]["max_rel_err"])
        assert v.wrong_groups == 0 and len(res.values) > 0
        assert v.max_rel_err <= MIX["checks"]["max_rel_err"]


def test_rehearsal_reads_the_join_layout(capsys):
    """The cell's traced rehearsal: correct, every window query a hit, and the
    join layout's metrics: one full layout and four joined instances, each
    padded to a power of two, every column four bytes but the padding's flag."""
    rc, line = rehearse(capsys, CELL, seed=2**31 + 29, trace=1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["join_s.setup"]["value"] > 0 and m["join_s.setup"]["unit"] == "s"
    assert m["sketch_rows_share"]["value"] > 0.5
    gen = _generate(2**31 + 29, 20_000)
    rows = len(gen["lineitem"][0]["l_orderkey"])
    pad = 1 << (rows - 1).bit_length()
    width = 4 * (16 + 9)
    assert m["join_mb.setup"]["value"] == pytest.approx(
        (rows * width + 4 * pad * (width + 1)) / 1e6)
