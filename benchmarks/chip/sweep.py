#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then a ladder of offered rates.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> --rates 50,100,150 [--seconds 10]

For each rate it builds the cell's stream at that rate (same seed, same
templates and thresholds), runs one open-loop window on the engine that
set-up left, checks every answer, and prints one JSON line: offered and
sustained rate (answered inside the window over its length), how late the
harness woke for arrivals, p50 and p95 latency from due time, the largest
batch, and how long the backlog took to drain after the window.  The knee
is the highest rate whose sustained rate equals the offered one and whose
backlog does not grow; the cell's fixed rate is set to about four fifths
of it in its traffic file.  The ladder stops after the first rate that
sustains less than nine tenths of what it offers.  Without a TPU it exits
non-zero.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    cell = harness.load_cell(harness.ROOT, args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib

    import jax
    import numpy as np

    from benchmarks.chip import traffic

    dev = jax.devices()
    if not args.rehearse and dev[0].platform != "tpu":
        print("sweep: no TPU found; no CPU fallback", file=sys.stderr)
        return 1
    if not args.rehearse:
        harness.enable_compile_cache(harness.ROOT)
    kernels_cm = contextlib.nullcontext()
    if args.rehearse:
        from repro.kernels import ops as kops

        kernels_cm = kops.forced_backend("interpret")
    with kernels_cm:
        prep = harness.prepare(cell, args.seed, args.seconds, rates[0], args.rehearse)
        print(json.dumps({"setup": prep.split}), flush=True)
        ok = True
        for rate in rates:
            stream = traffic.build(cell.traffic, prep.tables_np, args.seed,
                                   args.seconds, rate, prep.stream.exact)
            queries = [prep.query(j, thr) for j, thr in stream.queries]
            win = harness.run_window(prep.engine, queries, stream.due, args.seconds,
                                     lambda name: contextlib.nullcontext())
            lat = harness.latency_s(win, stream.due) * 1e3
            answered = [(j, thr, win.answers[i]) for i, (j, thr)
                        in enumerate(stream.queries) if win.answers[i] is not None]
            unanswered = int(np.isnan(win.done).sum())
            checks, _ = harness.check(stream, answered, cell.traffic["checks"],
                                      unanswered)
            correct = all(c["value"] <= c["limit"] for c in checks.values())
            ok &= correct
            late = np.asarray(win.lateness) * 1e3
            print(json.dumps({
                "offered_qps": rate, "queries": len(queries),
                "sustained_qps": float(np.sum(win.done <= args.seconds)) / args.seconds,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "lateness_ms_mean": float(late.mean()) if len(late) else 0.0,
                "lateness_ms_max": float(late.max()) if len(late) else 0.0,
                "max_batch": max((len(b) for b in win.batches), default=0),
                "drain_s": max(0.0, win.end - args.seconds),
                "correct": correct}), flush=True)
            if np.sum(win.done <= args.seconds) < 0.9 * len(queries):
                break
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
