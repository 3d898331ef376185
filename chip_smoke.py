#!/usr/bin/env python3
"""Drive the engine's main path once on a TPU and check every answer.

    python3 chip_smoke.py              # one chip, Chicago-crime scale
    python3 chip_smoke.py --chips 4    # only the sharded shard_map path, 4 chips
    python3 chip_smoke.py --rehearse   # same phases on the CPU, tiny, interpret kernels

One process drives everything: a chip belongs to one process, so nothing
here spawns a child that touches JAX (shards use the in-process loopback
transport).  The data comes from the seeded generators in
``repro.core.datasets``: ``make_crimes(6_700_000)`` is the paper's Chicago
Crime size (9 int32 columns), ``make_tpch(6_000_000)`` is TPC-H SF1
lineitem.

Phases on one chip: kernel exactness, kernel lowering, a cold Q-AGH HAVING
query under CB-OPT-GB (sample, AQR, size estimate, capture, execute), the
same query warm (an index hit), a ``run_batch`` mixing hits and misses (cold,
then warm), an append + delete followed by a hit that repairs its sketch, a
Q-AJGH join, and a 4-shard ``ShardedEngine`` hit batch.  Every answer is
compared with a numpy reference that shares no code with the engine
(``np.unique`` group keys, float64 ``np.bincount``, ``np.searchsorted``
join): values agree to a relative 1e-5 (float32 accumulation over ~18k rows
per group) and group membership is identical except for groups whose exact
aggregate lies within that tolerance of the HAVING threshold.

Each phase prints one JSON line: rows, wall seconds (the answers are host
arrays, so the clock stops after the device finished), XLA compiles counted
inside the phase (warm phases must compile nothing), the device's
``peak_bytes_in_use`` so far, and the parity result.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed on a TPU.  Without a TPU (and without ``--rehearse``) the script
exits non-zero; ``--rehearse`` never claims ``ok``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5

SCALES = {
    # rows of crimes, rows appended, rows of lineitem
    "chip": dict(crimes=6_700_000, append=67_000, lineitem=6_000_000),
    "rehearse": dict(crimes=30_000, append=3_000, lineitem=24_000),
}


# ---------------------------------------------------------------------------
# The numpy reference: no engine code
# ---------------------------------------------------------------------------

_CMP = {">": np.greater, ">=": np.greater_equal, "<": np.less,
        "<=": np.less_equal, "=": np.equal}


def reference_groups(q, tables):
    """Exact (float64) aggregate of every present group of ``q``'s inner
    block, keyed by the tuple of group-by values (as floats)."""
    cols = dict(tables[q.table])
    if q.join is not None:
        right = tables[q.join.right]
        rk = right[q.join.right_key]
        order = np.argsort(rk, kind="stable")
        lk = cols[q.join.left_key]
        pos = np.minimum(np.searchsorted(rk[order], lk), len(rk) - 1)
        hit = rk[order][pos] == lk
        ridx = order[pos[hit]]
        cols = {a: v[hit] for a, v in cols.items()}
        for a, v in right.items():
            cols.setdefault(a, v[ridx])
    n = len(next(iter(cols.values())))
    w = np.ones(n, np.float64)
    if q.where is not None:
        w = _CMP[q.where.op](cols[q.where.attr], q.where.value).astype(np.float64)
    codes, uniques = [], []
    for a in q.groupby:
        u, inv = np.unique(cols[a], return_inverse=True)
        codes.append(inv)
        uniques.append(u)
    flat = np.ravel_multi_index(codes, [len(u) for u in uniques])
    keys, gid = np.unique(flat, return_inverse=True)
    vals = (np.ones(n) if q.agg.attr is None
            else cols[q.agg.attr].astype(np.float64))
    sums = np.bincount(gid, weights=vals * w, minlength=len(keys))
    counts = np.bincount(gid, weights=w, minlength=len(keys))
    agg = {"sum": sums, "count": counts,
           "avg": sums / np.maximum(counts, 1.0)}[q.agg.fn]
    per_attr = np.unravel_index(keys, [len(u) for u in uniques])
    key_vals = [u[i].astype(np.float64) for u, i in zip(uniques, per_attr)]
    return {tuple(k): float(v) for k, v, c in zip(zip(*key_vals), agg, counts)
            if c > 0}


def check_answer(res, exact, q):
    """Parity of one engine answer with its exact reference groups."""
    got = {tuple(float(res.group_values[a][i]) for a in q.groupby):
           float(res.values[i]) for i in range(len(res.values))}
    h = q.having
    want = {k for k, v in exact.items() if h is None or _CMP[h.op](v, h.value)}
    boundary = set() if h is None else {
        k for k, v in exact.items()
        if abs(v - h.value) <= RTOL * max(abs(h.value), 1.0)}
    missing = (want - got.keys()) - boundary
    extra = (got.keys() - want) - boundary
    rel = [abs(got[k] - exact[k]) / max(abs(exact[k]), 1e-30)
           for k in got.keys() & exact.keys()]
    max_rel = max(rel, default=0.0)
    return {"groups": len(got), "expected": len(want),
            "boundary": len(boundary), "missing": len(missing),
            "extra": len(extra), "max_rel_err": max_rel,
            "ok": not missing and not extra and max_rel <= RTOL}


def threshold(q, tables, quantile):
    vals = np.array(sorted(reference_groups(q, tables).values()))
    return float(np.quantile(vals, quantile))


# ---------------------------------------------------------------------------
# Phase harness
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, jax, rehearse: bool):
        self.jax = jax
        self.rehearse = rehearse
        self.failed = []

    def peak_bytes(self):
        stats = self.jax.devices()[0].memory_stats()
        return None if not stats else stats.get("peak_bytes_in_use")

    def phase(self, name, fn, *, rows, warm=False):
        """Run one phase under a compile counter, print its JSON line, and
        return what ``fn`` returned (``None`` if it raised)."""
        from repro.runtime.guards import retrace_guard

        line = {"phase": name, "rows": rows}
        try:
            with retrace_guard(allowed=None) as watch:
                t0 = time.perf_counter()
                out, info = fn()
                self.jax.block_until_ready(out)
                line["wall_s"] = time.perf_counter() - t0
            line["compiles"] = watch.compiles
            line.update(info)
            ok = all(p.get("ok", True) for p in _parities(info))
            if warm and watch.compiles:
                ok = False
                line["error"] = "a warm phase compiled"
        except Exception:  # noqa: BLE001 - a failed phase is reported, then fails the run
            line["error"] = traceback.format_exc(limit=6)
            out, ok = None, False
        line["peak_bytes_in_use"] = self.peak_bytes()
        line["ok"] = ok
        print(json.dumps(line, default=_jsonable), flush=True)
        if not ok:
            self.failed.append(name)
        return out


def _parities(info):
    for v in info.values():
        if isinstance(v, dict) and "ok" in v:
            yield v
        elif isinstance(v, list):
            yield from (p for p in v if isinstance(p, dict) and "ok" in p)


def _jsonable(x):
    if isinstance(x, (np.integer, np.floating)):
        return x.item()
    if isinstance(x, np.bool_):
        return bool(x)
    return str(x)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def exactness_phase(jax, jnp, kops, rehearse):
    """segment_aggregate(_batch) on integral values above 256 with per-group
    sums below 2^24 must equal float64 numpy bit for bit."""
    rng = np.random.default_rng(7)
    n, g = (1 << 20, 512) if not rehearse else (1 << 14, 64)
    gid = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(257, 4097, n).astype(np.float32)
    w = (rng.random(n) < 0.75).astype(np.float32)
    want = np.bincount(gid, weights=vals.astype(np.float64) * w, minlength=g)
    want_c = np.bincount(gid, weights=w.astype(np.float64), minlength=g)
    assert want.max() < 2 ** 24
    d_gid, d_vals, d_w = jnp.asarray(gid), jnp.asarray(vals), jnp.asarray(w)

    def exact(s, c):
        s, c = np.asarray(s, np.float64), np.asarray(c, np.float64)
        return {"ok": bool((s == want).all() and (c == want_c).all()),
                "max_abs_err": float(np.abs(s - want).max())}

    s, c = kops.segment_aggregate(d_vals, d_gid, g, d_w)
    kernel = exact(s, c)
    b = 4
    sb, cb = kops.segment_aggregate_batch(
        jnp.tile(d_vals, (b, 1)), jnp.tile(d_gid, (b, 1)), g, jnp.tile(d_w, (b, 1)))
    batch = [exact(sb[i], cb[i]) for i in range(b)]
    s_ref, c_ref = kops.segment_aggregate(d_vals, d_gid, g, d_w, backend="ref")
    ref_path = exact(s_ref, c_ref)
    # The precision probe: the reference path's one-hot einsum at default
    # precision (what it used before it asked for HIGHEST).  Reported, not
    # required.
    m = 1 << 14
    onehot = (d_gid[None, :m, None] == jnp.arange(g)).astype(jnp.float32)
    vw = jnp.stack([d_vals[:m] * d_w[:m], d_w[:m]])[None]
    probe = jnp.einsum("bkn,bng->bkg", vw, onehot)[0, 0]
    want_m = np.bincount(gid[:m], weights=vals[:m].astype(np.float64) * w[:m],
                         minlength=g)
    probe_err = float(np.abs(np.asarray(probe, np.float64) - want_m).max())
    return (s, sb), {"kernel": kernel, "kernel_batch": batch,
                     "ref_path_highest": ref_path,
                     "default_precision_einsum_max_abs_err": probe_err}


def lowering_phase(jax, jnp, kops, rehearse):
    """Each of the five kernels lowers to a Mosaic custom call on the chip."""
    n = 1 << 15
    i32 = jnp.zeros(n, jnp.int32)
    f32 = jnp.zeros(n, jnp.float32)
    mask = jnp.zeros(n, bool)
    cases = {
        "segment_aggregate": (lambda v, g, w: kops.segment_aggregate(v, g, 512, w),
                              (f32, i32, f32)),
        "segment_aggregate_batch": (
            lambda v, g, w: kops.segment_aggregate_batch(v, g, 128, w),
            (f32.reshape(4, -1), i32.reshape(4, -1), f32.reshape(4, -1))),
        "fragment_bitmap": (lambda p, b: kops.fragment_bitmap(p, b, 1000), (mask, i32)),
        "fragment_bitmap_batch": (lambda p, b: kops.fragment_bitmap_batch(p, b, 1000),
                                  (mask.reshape(1, -1).repeat(16, 0), i32)),
        "sketch_filter": (lambda b, bits: kops.sketch_filter(b, bits),
                          (i32, jnp.zeros(1000, bool))),
    }
    native = {name: "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()
              for name, (fn, args) in cases.items()}
    parity = {"ok": rehearse or all(native.values())}
    return None, {"tpu_custom_call": native, "lowering": parity}


def crimes_queries(tables):
    from repro.core import Aggregate, Having, Predicate, Query

    def having(q, quantile):
        return dataclasses.replace(
            q, having=Having(">", threshold(q, tables, quantile)))

    base = Query("crimes", ("district", "year"), Aggregate("sum", "records"))
    q1 = having(base, 0.9)
    batch = [
        q1,  # hit
        having(base, 0.97),  # subsumed by q1's sketch: hit
        having(Query("crimes", ("district", "month"), Aggregate("sum", "records")), 0.9),
        having(Query("crimes", ("ward", "year"), Aggregate("sum", "records")), 0.9),
        having(Query("crimes", ("community",), Aggregate("count")), 0.8),
        having(dataclasses.replace(base, where=Predicate("month", ">=", 6.0)), 0.9),
        having(Query("crimes", ("zipcode",), Aggregate("sum", "records")), 0.9),
        having(Query("crimes", ("year", "month"), Aggregate("avg", "records")), 0.7),
    ]
    batch.append(batch[2])  # a repeat: deferred within the batch, then a hit
    return q1, batch


def crimes_tables(table):
    return {"crimes": table.to_numpy()}


def run_one_chip(smoke, jax, jnp, scale):
    from repro.core import (Aggregate, Database, Having, JoinSpec, PBDSEngine,
                            Query, ShardedEngine)
    from repro.core.datasets import make_crimes, make_tpch
    from repro.kernels import ops as kops

    smoke.phase("exactness", lambda: exactness_phase(jax, jnp, kops, smoke.rehearse),
                rows=None)
    smoke.phase("lowering", lambda: lowering_phase(jax, jnp, kops, smoke.rehearse),
                rows=None)
    dispatch_base = collections.Counter(kops.DISPATCHES)

    n = scale["crimes"]
    t0 = time.perf_counter()
    crimes = make_crimes(n)
    jax.block_until_ready(list(crimes.columns.values()))
    t_load = time.perf_counter() - t0
    tables = crimes_tables(crimes)
    q1, batch = crimes_queries(tables)
    exact_q1 = reference_groups(q1, tables)
    eng = PBDSEngine(Database({"crimes": crimes}), strategy="CB-OPT-GB")
    print(json.dumps({"phase": "load", "table": "crimes", "rows": n,
                      "columns": len(crimes.columns),
                      "device_bytes": int(sum(v.nbytes for v in crimes.columns.values())),
                      "wall_s": t_load}), flush=True)

    def cold():
        res, info = eng.run(q1)
        return res.values, {"created": info.created, "attr": info.attr,
                            "t_select": info.t_select, "t_capture": info.t_capture,
                            "t_execute": info.t_execute,
                            "parity": check_answer(res, exact_q1, q1),
                            "path": {"ok": info.created}}

    def warm():
        res, info = eng.run(q1)
        return res.values, {"reused": info.reused,
                            "parity": check_answer(res, exact_q1, q1),
                            "path": {"ok": info.reused}}

    smoke.phase("cold_agh", cold, rows=n)
    smoke.phase("warm_agh", warm, rows=n, warm=True)

    exact_batch = [reference_groups(q, tables) for q in batch]

    def run_batch():
        out = eng.run_batch(batch)
        return [r.values for r, _ in out], {
            "queries": len(batch),
            "hits": sum(i.reused for _, i in out),
            "created": sum(i.created for _, i in out),
            "parity": [check_answer(r, e, q)
                       for (r, _), e, q in zip(out, exact_batch, batch)]}

    smoke.phase("batch_cold", run_batch, rows=n)
    smoke.phase("batch_warm", run_batch, rows=n, warm=True)

    # -- append + delete, then a hit that repairs its sketch ---------------------
    app = make_crimes(scale["append"], seed=1).to_numpy()
    before = tables["crimes"]
    grown = {a: np.concatenate([before[a], app[a]]) for a in before}
    rng = np.random.default_rng(3)
    dead = rng.random(len(grown["records"])) < 0.001
    after = {"crimes": {a: v[~dead] for a, v in grown.items()}}
    exact_mut = reference_groups(q1, after)

    def mutate():
        eng.append_rows("crimes", app)
        eng.delete_rows("crimes", dead)
        res, info = eng.run(q1)
        return res.values, {"appended": scale["append"], "deleted": int(dead.sum()),
                            "repaired": info.repaired, "t_repair": info.t_repair,
                            "parity": check_answer(res, exact_mut, q1),
                            "path": {"ok": info.reused and info.repaired}}

    smoke.phase("mutate_repair", mutate, rows=int((~dead).sum()))

    # -- 4 shards on this chip: the fused stacked launch ------------------------
    def sharded():
        se = ShardedEngine(Database({"crimes": crimes}), "crimes", "district",
                           n_shards=4, transport="loopback")
        se.run(q1)
        hits = batch[:2]
        out = se.run_batch(hits)
        route = se.last_route
        parity = [check_answer(r, exact_batch[i], q) for i, ((r, _), q)
                  in enumerate(zip(out, hits))]
        return [r.values for r, _ in out], {
            "fused": route.fused, "launch_devices": route.launch_devices,
            "parity": parity,
            "path": {"ok": route.fused and all(i.reused for _, i in out)}}

    smoke.phase("sharded_hits", sharded, rows=n)
    del eng, crimes

    # -- Q-AJGH on TPC-H SF1 lineitem ------------------------------------------
    nl = scale["lineitem"]
    tpch = make_tpch(nl)
    ttables = {k: t.to_numpy() for k, t in tpch.tables.items()}
    qj = Query("lineitem", ("l_quantity", "o_shippriority"),
               Aggregate("sum", "l_quantity"),
               join=JoinSpec("orders", "l_orderkey", "o_orderkey"))
    qj = dataclasses.replace(qj, having=Having(">", threshold(qj, ttables, 0.8)))
    exact_j = reference_groups(qj, ttables)
    teng = PBDSEngine(tpch, strategy="CB-OPT-GB")

    def join():
        res, info = teng.run(qj)
        return res.values, {"created": info.created, "reused": info.reused,
                            "attr": info.attr,
                            "parity": check_answer(res, exact_j, qj)}

    smoke.phase("join_ajgh_cold", join, rows=nl)
    smoke.phase("join_ajgh_warm", join, rows=nl, warm=True)

    used = kops.DISPATCHES - dispatch_base
    modes = collections.defaultdict(dict)
    for (kernel, mode), k in sorted(used.items()):
        modes[kernel][mode] = k
    want = "interpret" if smoke.rehearse else "pallas"
    five = ("segment_aggregate", "segment_aggregate_batch", "fragment_bitmap",
            "fragment_bitmap_batch", "sketch_filter")
    ok = (all(modes.get(k, {}).get(want, 0) > 0 for k in five)
          and all(set(m) == {want} for m in modes.values()))
    print(json.dumps({"phase": "kernel_dispatch", "modes": modes, "ok": ok}),
          flush=True)
    if not ok:
        smoke.failed.append("kernel_dispatch")


def run_four_chips(smoke, jax, scale):
    """The sharded path only: ShardedEngine(n_shards=4) against a
    single-node PBDSEngine, both in this process."""
    from repro.core import Database, PBDSEngine, ShardedEngine
    from repro.core.datasets import make_crimes
    from repro.core.shard import LAUNCH_COUNTS

    n = scale["crimes"]
    crimes = make_crimes(n)
    tables = crimes_tables(crimes)
    q1, batch = crimes_queries(tables)
    hits = batch[:2]
    exact = [reference_groups(q, tables) for q in hits]
    single = PBDSEngine(Database({"crimes": crimes}), strategy="CB-OPT-GB")
    se = ShardedEngine(Database({"crimes": crimes}), "crimes", "district",
                       n_shards=4, transport="loopback")

    single_results = []

    def reference():
        single.run(q1)
        out = single.run_batch(hits)
        single_results[:] = [r for r, _ in out]
        return [r.values for r, _ in out], {
            "hits": sum(i.reused for _, i in out),
            "parity": [check_answer(r, e, q) for (r, _), e, q in zip(out, exact, hits)],
            "path": {"ok": all(i.reused for _, i in out)}}

    def sharded(cold):
        def fn():
            if cold:
                se.run(q1)
            spmd = LAUNCH_COUNTS["fused_spmd"]
            out = se.run_batch(hits)
            route = se.last_route
            same = [r.canonical() == s.canonical()
                    for (r, _), s in zip(out, single_results)]
            ok = (route.fused and route.launch_devices == len(jax.devices())
                  and LAUNCH_COUNTS["fused_spmd"] > spmd
                  and len(same) == len(hits) and all(same)
                  and all(i.reused for _, i in out))
            return [r.values for r, _ in out], {
                "shard_map_launches": LAUNCH_COUNTS["fused_spmd"] - spmd,
                "launch_devices": route.launch_devices,
                "equal_to_single_node": same,
                "parity": [check_answer(r, e, q)
                           for (r, _), e, q in zip(out, exact, hits)],
                "path": {"ok": ok}}
        return fn

    smoke.phase("single_node", reference, rows=n)
    smoke.phase("sharded_4_cold", sharded(True), rows=n)
    smoke.phase("sharded_4_warm", sharded(False), rows=n, warm=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded shard_map path")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on the CPU at a tiny size with "
                         "interpret-mode kernels; never claims ok")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke.py: the repository's src/repro is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=4")

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print("chip_smoke.py: no TPU found; this script measures the chip "
              "and does not fall back to the CPU (use --rehearse)",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(json.dumps({"devices": device}), flush=True)

    smoke = Smoke(jax, args.rehearse)
    scale = SCALES["rehearse" if args.rehearse else "chip"]
    # On the chip the engine's own default (native Pallas) runs untouched.
    with (kops.forced_backend("interpret") if args.rehearse
          else contextlib.nullcontext()):
        if args.chips == 4:
            run_four_chips(smoke, jax, scale)
        else:
            run_one_chip(smoke, jax, jnp, scale)
    if smoke.failed:
        print(f"chip_smoke.py: failed phases: {smoke.failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
