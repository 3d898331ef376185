"""The reference and its comparison catch wrong answers; the join follows
the engine's semantics; the lower-precision control fails the limits; the
crimes data is pinned; the traffic generator gives every seed the same work;
the roofline's bytes are right on stated shapes."""
import hashlib
import json
import os

import ml_dtypes
import numpy as np
import pytest

from chipbench_util import ROOT

from benchmarks.chip import datagen, reference, roofline, traffic


def _traffic(name):
    return traffic.load(os.path.join(ROOT, "benchmarks", "chip", "traffic", f"{name}.json"))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def crimes():
    gen = datagen.generate(_config("crimes-synth-6.7m"), seed=3, scale=0.01)
    return {k: cols for k, (cols, _) in gen.items()}


def _answer(exact, groupby, thr):
    return reference.answer_from_reference(exact, groupby, ">", thr)


def test_reference_matches_a_loop(crimes):
    t = traffic.templates(_traffic("qagh-hits"))[0]
    ex = reference.exact_groups(t, crimes)
    want = {}
    c = crimes["crimes"]
    for row in zip(*[c[a] for a in t.groupby], c[t.agg_attr]):
        key = tuple(float(v) for v in row[:-1])
        want[key] = want.get(key, 0) + int(row[-1])
    assert dict(zip(ex.keys, ex.values.tolist())) == want


def _join_template(groupby, agg):
    return traffic.Template("J", "fact", tuple(groupby), agg[0], agg[1],
                            traffic.Join("dim", "f_key", "d_key"))


@pytest.fixture
def star():
    rng = np.random.default_rng(8)
    fact = {"f_key": rng.integers(0, 30, 500).astype(np.int32),  # keys 25..29: no partner
            "f_day": rng.integers(1, 5, 500).astype(np.int32),
            "f_amount": rng.integers(1, 50, 500).astype(np.int32)}
    dim = {"d_key": rng.permutation(25).astype(np.int32),
           "d_region": rng.integers(1, 4, 25).astype(np.int32)}
    return {"fact": fact, "dim": dim}


@pytest.mark.parametrize("groupby,agg", [
    (["d_region"], ["sum", "f_amount"]),
    (["f_day", "d_region"], ["avg", "f_amount"]),
    (["d_region"], ["count", None]),
])
def test_join_reference_matches_a_dict_join(star, groupby, agg):
    """Inner join: each fact row with its one partner, unmatched fact rows
    dropped; group-by attributes from either table."""
    partner = {int(k): i for i, k in enumerate(star["dim"]["d_key"])}
    sums, counts = {}, {}
    fact, dim = star["fact"], star["dim"]
    for r in range(len(fact["f_key"])):
        i = partner.get(int(fact["f_key"][r]))
        if i is None:
            continue
        row = {**{a: v[r] for a, v in fact.items()}, **{a: v[i] for a, v in dim.items()}}
        key = tuple(float(row[a]) for a in groupby)
        sums[key] = sums.get(key, 0) + (int(row[agg[1]]) if agg[1] else 1)
        counts[key] = counts.get(key, 0) + 1
    want = {k: {"sum": sums[k], "count": counts[k], "avg": sums[k] / counts[k]}[agg[0]]
            for k in sums}
    assert sum(counts.values()) < len(fact["f_key"])  # some rows had no partner
    ex = reference.exact_groups(_join_template(groupby, agg), star)
    assert dict(zip(ex.keys, ex.values.tolist())) == pytest.approx(want)


def test_join_reference_refuses_what_the_engine_would_rename_or_repeat(star):
    t = _join_template(["d_region"], ["sum", "f_amount"])
    clash = dict(star, dim=dict(star["dim"], f_day=star["dim"]["d_region"]))
    with pytest.raises(ValueError, match="both sides"):
        reference.exact_groups(t, clash)
    twice = dict(star["dim"], d_key=star["dim"]["d_key"] % 20)
    with pytest.raises(ValueError, match="not unique"):
        reference.exact_groups(t, dict(star, dim=twice))


def test_crimes_data_is_pinned():
    """The crimes generator gives the bytes it gave before it moved into
    ``generators/crimes.py``: same draws, same order, same dtypes."""
    gen = datagen.generate(_config("crimes-synth-6.7m"), seed=3, scale=0.01)
    assert list(gen) == ["crimes"]
    cols, pk = gen["crimes"]
    assert pk == ("beat", "year", "month") and len(cols["pid"]) == 67_000
    h = hashlib.sha256()
    for name in sorted(cols):
        h.update(name.encode())
        h.update(str(cols[name].dtype).encode())
        h.update(cols[name].tobytes())
    assert h.hexdigest() == "96f13326631b98c5f1ded77a723407d2e56367e60a49df3e1858715615aa0964"


def test_compare_catches_planted_wrong_answers(crimes):
    t = traffic.templates(_traffic("qagh-hits"))[0]
    ex = reference.exact_groups(t, crimes)
    idx = ex.index()
    thr = float(np.quantile(ex.values, 0.9))
    gv, vals = _answer(ex, t.groupby, thr)
    ok = reference.compare(t.groupby, gv, vals, ex, idx, ">", thr, 1e-6)
    assert (ok.wrong_groups, ok.max_rel_err) == (0, 0.0)

    off = vals.copy()
    off[0] += 1.0
    assert reference.compare(t.groupby, gv, off, ex, idx, ">", thr, 1e-6).max_rel_err > 1e-6

    dropped = {a: v[1:] for a, v in gv.items()}
    v = reference.compare(t.groupby, dropped, vals[1:], ex, idx, ">", thr, 1e-6)
    assert v.missing == 1 and v.extra == 0

    # A wrong hit: a sketch captured at a higher threshold answers a lower
    # one, so the groups in between come back short or not at all.
    hi = float(np.quantile(ex.values, 0.97))
    gv_hi, vals_hi = _answer(ex, t.groupby, hi)
    v = reference.compare(t.groupby, gv_hi, vals_hi, ex, idx, ">", thr, 1e-6)
    assert v.missing > 0


def test_groups_at_the_threshold_may_fall_either_side(crimes):
    t = traffic.templates(_traffic("qagh-hits"))[2]
    ex = reference.exact_groups(t, crimes)
    i = int(np.argsort(ex.values)[len(ex.values) // 2])
    thr = float(ex.values[i]) * (1 - 1e-7)  # group i passes, by a hair
    gv, vals = _answer(ex, t.groupby, thr)
    keep = np.array([k != ex.keys[i] for k in zip(*[gv[a] for a in t.groupby])])
    v = reference.compare(t.groupby, {a: x[keep] for a, x in gv.items()}, vals[keep],
                          ex, ex.index(), ">", thr, 1e-6)
    assert v.wrong_groups == 0
    v = reference.compare(t.groupby, {a: x[keep] for a, x in gv.items()}, vals[keep],
                          ex, ex.index(), ">", thr, 1e-9)
    assert v.missing == 1


def test_bf16_control_fails_the_limit():
    """The reference in place of the engine, with the aggregated column in
    bfloat16, fails the cell's value limit."""
    gen = datagen.generate(_config("crimes-synth-6.7m"), seed=4, scale=0.02)
    tables = {k: cols for k, (cols, _) in gen.items()}
    mix = _traffic("qagh-hits")
    stream = traffic.build(mix, tables, seed=4, seconds=1.0, rate=200)
    worst = 0.0
    for j, t in enumerate(stream.templates):
        ex = stream.exact[j]
        ctl = reference.exact_groups(t, tables, value_dtype=ml_dtypes.bfloat16)
        for jj, thr in stream.queries:
            if jj != j:
                continue
            gv, vals = _answer(ctl, t.groupby, thr)
            v = reference.compare(t.groupby, gv, vals, ex, ex.index(), ">", thr,
                                  mix["checks"]["max_rel_err"])
            worst = max(worst, v.max_rel_err)
    assert worst > mix["checks"]["max_rel_err"]


def test_every_seed_gets_the_same_work(crimes):
    mix = _traffic("qagh-hits")
    a = traffic.build(mix, crimes, seed=1, seconds=2.0, rate=100)
    b = traffic.build(mix, crimes, seed=2**33 + 1, seconds=2.0, rate=100)
    again = traffic.build(mix, crimes, seed=1, seconds=2.0, rate=100)
    assert a.queries == again.queries and np.array_equal(a.due, again.due)
    assert len(a.queries) == len(b.queries) == 200
    assert a.queries != b.queries  # the seed orders each template's thresholds
    # One arrival schedule: the same templates arrive at the same times.
    assert [j for j, _ in a.queries] == [j for j, _ in b.queries]
    np.testing.assert_array_equal(a.due, b.due)
    for j in range(4):
        assert sorted(t for jj, t in a.queries if jj == j) == \
            sorted(t for jj, t in b.queries if jj == j)
    counts = np.bincount([j for j, _ in a.queries])
    assert counts.tolist() == [50, 50, 50, 50]  # uniform over 4 templates
    setup = dict(a.setup)
    assert all(thr >= setup[j] for j, thr in a.queries)  # every query subsumed
    assert a.due[-1] < 2.0 and np.all(np.diff(a.due) > 0)


def test_aggregate_bytes_on_stated_shapes():
    # 1M rows of (4 B group code + 4 B value), 375 groups of 8 B written.
    assert roofline.aggregate_bytes(1_000_000, 375, True, False) == 8_000_000 + 3_000
    # count reads no value; a WHERE column adds 4 B per row.
    assert roofline.aggregate_bytes(1_000, 77, False, False) == 4_000 + 616
    assert roofline.aggregate_bytes(1_000, 77, True, True) == 12_000 + 616
    peak = roofline.peaks("TPU v5 lite")
    t = roofline.least_time_s(roofline.aggregate_ops(1_000_000, False),
                              roofline.aggregate_bytes(1_000_000, 375, True, False), peak)
    assert t == pytest.approx(8_003_000 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
