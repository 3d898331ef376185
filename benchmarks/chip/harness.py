"""One run of one cell: set-up, an open-loop window, the check, the line.

Set-up (counted in ``setup_s``, from process start to the window's start):
generate the configuration's tables from the seed, load them onto the
device, build the seeded query stream, start the engine with the cell's
settings (``Cell.engine``), capture the mix's sketches where the traffic
file asks for captures (``PBDSEngine.run_batch`` over one query per
template at the set-up quantile, which must create a sketch for each), and
warm up every template on the path the window takes.

Window: arrivals are due at the stream's fixed offsets.  At each step the
harness takes every query that is due and unanswered and passes them all in
one ``run_batch`` call; when none is due it sleeps until the next one.  A
query's latency runs from its due time to the moment its answer is on the
host.  Queries still unanswered a minute after the window count as failed.

Check (after the window, once the device peak has been read and the engine
freed): every answer of set-up, warm-up and window against the exact numpy
reference, with the limits of the traffic file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.chip import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GRACE_S = 60.0
# The CPU rehearsal (tests only): tables cut to about this many rows, a slow
# offered rate, interpret-mode kernels.  It never runs on a chip.
REHEARSE_ROWS = 20_000
REHEARSE_RATE = 6.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SetupError(RuntimeError):
    """The cell cannot be set up as its files describe (no result)."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``; 0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


# ---------------------------------------------------------------------------
# Cells, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    @property
    def engine(self) -> dict:
        """The engine's settings: the configuration's, with the traffic
        file's ``engine`` laid over them."""
        return {**self.config["engine"], **self.traffic.get("engine", {})}

    def metric_module(self, name: str):
        return load_module(self.root, "metrics", name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks", "chip", "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                root=root)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunRecord:
    """What one run leaves for the per-layer metric readers."""

    stream: object  # traffic.Stream
    setup_infos: list  # RunInfo of each set-up capture
    window_infos: list  # RunInfo (or None) per window query, arrival order
    batches: List[List[int]]  # window query indices of each run_batch call
    compiles: int  # XLA compilations inside the window
    trace: Optional[object]  # trace_reduce.Summary of a --trace 1 run
    live_rows: Dict[str, int]  # rows of each table
    groups: List[int]  # exact group count of each template
    device_kind: str
    catalog_misses: Optional[int] = None  # the engine catalog's, inside the window


@dataclasses.dataclass
class Window:
    answers: list
    infos: list
    done: np.ndarray  # seconds after the window opened; nan = unanswered
    batches: List[List[int]]
    errors: List[str]
    lateness: List[float]  # wake-up minus due, for each sleep
    end: float


def run_window(engine, queries: Sequence, due: np.ndarray, seconds: float,
               annotate) -> Window:
    n = len(queries)
    answers: list = [None] * n
    infos: list = [None] * n
    done = np.full(n, np.nan)
    batches, errors, lateness = [], [], []
    nxt = 0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while nxt < n:
            now = time.perf_counter() - t0
            if now > seconds + GRACE_S:
                break
            upto = int(np.searchsorted(due, now, side="right"))
            if upto > nxt:
                batch = list(range(nxt, upto))
                nxt = upto
                try:
                    with annotate("bench.run_batch"):
                        out = engine.run_batch([queries[i] for i in batch])
                except Exception:  # noqa: BLE001 - a failed batch fails its queries
                    errors.append(traceback.format_exc(limit=8))
                    out = []
                t = time.perf_counter() - t0
                with annotate("bench.collect"):
                    for i, item in zip(batch, out):
                        answers[i], infos[i] = item
                        done[i] = t
                batches.append(batch)
                continue
            with annotate("bench.wait"):
                time.sleep(max(0.0, due[nxt] - now))
            lateness.append(time.perf_counter() - t0 - due[nxt])
    return Window(answers, infos, done, batches, errors, lateness,
                  end=time.perf_counter() - t0)


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache in a fixed directory inside the checkout (or
    the one ``JAX_COMPILATION_CACHE_DIR`` names), every program kept, so
    that only a cell's first run there compiles."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv: Optional[Sequence[str]] = None, root: str = ROOT) -> int:
    import argparse

    t_top = time.perf_counter()
    age_at_top = process_age_s()

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny tables, interpret-mode kernels (tests only)")
    args = ap.parse_args(argv)

    try:
        cell = load_cell(root, args.workload)
    except (SetupError, OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"benchmark: the system under test is not at {src}", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). No CPU fallback.",
              file=sys.stderr)
        return 1
    if not args.rehearse:
        enable_compile_cache(root)

    kernels_cm = contextlib.nullcontext()
    if args.rehearse:
        from repro.kernels import ops as kops

        kernels_cm = kops.forced_backend("interpret")
    try:
        with kernels_cm:
            return _run(cell, args, devices, t_top, age_at_top)
    except SetupError as e:
        print(f"benchmark: set-up failed: {e}", file=sys.stderr)
        return 3


@dataclasses.dataclass
class Prepared:
    """Everything set-up leaves for the window."""

    engine: object
    db: object
    tables_np: dict
    stream: object  # traffic.Stream
    query: object  # (template, threshold) -> engine Query
    checked: list  # (template, threshold, QueryResult) of set-up and warm-up
    setup_infos: list
    missing: int  # set-up and warm-up queries that got no answer
    split: Dict[str, float]


def prepare(cell: Cell, seed: int, seconds: float, rate: Optional[float],
            rehearse: bool) -> Prepared:
    """Tables, stream, engine, captures and warm-up of one cell."""
    import jax

    from benchmarks.chip import datagen, traffic
    from repro.core import Aggregate, Database, Having, JoinSpec, PBDSEngine, Query
    from repro.core.table import from_numpy

    split = {}
    t = time.perf_counter()
    scale = 1.0
    if rehearse:
        scale = min(1.0, REHEARSE_ROWS / max(cell.config["generator"]["rows"].values()))
        rate = min(rate or cell.traffic["rate_qps"], REHEARSE_RATE)
    gen = datagen.generate(cell.config, seed, scale, cell.root)
    tables_np = {name: cols for name, (cols, _) in gen.items()}
    split["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    db = Database({name: from_numpy(name, cols, pk) for name, (cols, pk) in gen.items()})
    jax.block_until_ready([t_.columns for t_ in db.tables.values()])
    split["load_s"] = time.perf_counter() - t

    t = time.perf_counter()
    stream = traffic.build(cell.traffic, tables_np, seed, seconds, rate)
    split["traffic_s"] = time.perf_counter() - t

    def query(j: int, thr: float) -> Query:
        tp = stream.templates[j]
        return Query(tp.table, tp.groupby, Aggregate(tp.agg_fn, tp.agg_attr),
                     having=Having(stream.op, thr),
                     join=None if tp.join is None else JoinSpec(*tp.join))

    engine = PBDSEngine(db, **cell.engine)

    checked = []
    t = time.perf_counter()
    setup_infos = []
    missing = 0
    if stream.setup:
        out = engine.run_batch([query(j, thr) for j, thr in stream.setup])
        missing += len(stream.setup) - len(out)
        for (j, thr), (res, info) in zip(stream.setup, out):
            if not info.created:
                raise SetupError(
                    f"the engine declined a sketch for template "
                    f"{stream.templates[j].name} at threshold {thr}")
            checked.append((j, thr, res))
            setup_infos.append(info)
    split["captures_s"] = time.perf_counter() - t

    t = time.perf_counter()
    warm = [query(j, thr) for j, thr in stream.warmup]
    k = len(stream.templates)
    for (j, thr), q in zip(stream.warmup[:k], warm[:k]):
        out = engine.run_batch([q])
        missing += 1 - len(out)
        checked += [(j, thr, res) for res, _ in out]
    out = engine.run_batch(warm)
    missing += len(warm) - len(out)
    checked += [(j, thr, res) for (j, thr), (res, _) in zip(stream.warmup, out)]
    split["warmup_s"] = time.perf_counter() - t
    return Prepared(engine, db, tables_np, stream, query, checked, setup_infos,
                    missing, split)


def check(stream, checked: list, limits: dict, unanswered: int) -> Tuple[dict, List[int]]:
    """Every answer against the exact reference groups the stream was built
    from: each compared number with its limit."""
    from benchmarks.chip import reference

    exact = stream.exact
    index = [e.index() for e in exact]
    max_rel, wrong = 0.0, 0
    for j, thr, res in checked:
        v = reference.compare(stream.templates[j].groupby, res.group_values, res.values,
                              exact[j], index[j], stream.op, thr, limits["max_rel_err"])
        max_rel = max(max_rel, v.max_rel_err)
        wrong += v.wrong_groups
    checks = {"max_rel_err": {"value": float(max_rel), "limit": limits["max_rel_err"]},
              "wrong_groups": {"value": wrong, "limit": limits["wrong_groups"]},
              "unanswered": {"value": unanswered, "limit": limits["unanswered"]}}
    return checks, [len(e.keys) for e in exact]


def latency_s(win: Window, due: np.ndarray) -> np.ndarray:
    """From due time to answer; an unanswered query counts until the end."""
    return np.where(np.isnan(win.done), win.end, win.done) - due


def _run(cell: Cell, args, devices, t_top: float, age_at_top: float) -> int:
    import jax
    from jax import monitoring

    from benchmarks.chip import trace_reduce

    prep = prepare(cell, args.seed, args.seconds, None, args.rehearse)
    engine, stream, query, checked = prep.engine, prep.stream, prep.query, prep.checked
    queries = [query(j, thr) for j, thr in stream.queries]
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    trace_dir = None
    if args.trace:
        from jax.profiler import ProfileOptions, TraceAnnotation, start_trace

        annotate = TraceAnnotation
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        start_trace(trace_dir, profiler_options=opts)

    compiles = []

    def on_event(name: str, secs: float, **kw) -> None:
        if name == COMPILE_EVENT:
            compiles.append(secs)

    gc.collect()
    gc.freeze()
    setup_s = age_at_top + (time.perf_counter() - t_top)
    misses_before = engine.catalog.misses()
    monitoring.register_event_duration_secs_listener(on_event)
    try:
        win = run_window(engine, queries, stream.due, args.seconds, annotate)
    finally:
        monitoring.unregister_event_duration_listener(on_event)
        if args.trace:
            jax.profiler.stop_trace()
    gc.unfreeze()
    catalog_misses = engine.catalog.misses() - misses_before

    peak = 0
    for d in devices[:cell.chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    live_rows = {name: prep.db[name].num_rows for name in prep.db.names}
    del engine
    prep.engine = prep.db = None
    gc.collect()

    summary = None
    if trace_dir is not None:
        path = trace_reduce.find_xplane(trace_dir)
        events = trace_reduce.load_events(path) if path else []
        shutil.rmtree(trace_dir, ignore_errors=True)
        with open(os.path.join(HERE, "kernels.json")) as f:
            summary = trace_reduce.summarize(events, json.load(f))

    # -- the check --------------------------------------------------------
    t = time.perf_counter()
    checked += [(j, thr, win.answers[i]) for i, (j, thr) in enumerate(stream.queries)
                if win.answers[i] is not None]
    unanswered = int(np.isnan(win.done).sum())
    checks, groups = check(stream, checked, cell.traffic["checks"],
                           unanswered + prep.missing)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    check_s = time.perf_counter() - t

    # -- metrics ----------------------------------------------------------
    n = len(queries)
    lat = latency_s(win, stream.due)
    e2e = {
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "qps": float(np.sum(win.done <= args.seconds)) / args.seconds,
        "setup_s": setup_s,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": n,
            "failed": unanswered, "metrics": {}, "device": device}
    if args.trace:
        record = RunRecord(stream=stream, setup_infos=prep.setup_infos,
                           window_infos=win.infos, batches=win.batches,
                           compiles=len(compiles), trace=summary,
                           live_rows=live_rows, groups=groups,
                           device_kind=devices[0].device_kind,
                           catalog_misses=catalog_misses)
        for m in cell.per_layer:
            value = cell.metric_module(m["name"]).read(record)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            if summary.busy_s is not None:
                device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            line["breakdown"] = {"device_ops": summary.device_ops,
                                 "idle_gaps": summary.idle_gaps}
    else:
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    if args.rehearse:
        line["rehearsal"] = True
    line["checks"] = checks

    late = np.asarray(win.lateness) * 1e3
    sizes = [len(b) for b in win.batches]
    log = sys.stderr
    print("setup " + " ".join(f"{k}={v:.3f}" for k, v in prep.split.items())
          + f" setup_s={setup_s:.3f}", file=log)
    print(f"window offered={n} answered={n - unanswered} batches={len(sizes)} "
          f"max_batch={max(sizes, default=0)} window_end_s={win.end:.3f} "
          f"compiles={len(compiles)} catalog_misses={catalog_misses} "
          f"errors={len(win.errors)} "
          f"lateness_ms_mean={late.mean() if len(late) else 0.0:.4f} "
          f"lateness_ms_max={late.max() if len(late) else 0.0:.4f}", file=log)
    hits = sum(1 for i in win.infos if i is not None and i.reused)
    print(f"window hits={hits} p50_ms={e2e['p50_ms']:.4f} p95_ms={e2e['p95_ms']:.4f} "
          f"qps={e2e['qps']:.3f} memory_peak_bytes={peak} check_s={check_s:.3f}",
          file=log)
    for err in win.errors[:3]:
        print(err, file=log)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=log)
    print(json.dumps(line), flush=True)
    return 0
