"""Benchmark harness: one entry per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale quick|full] [--only NAME] [--json]

Emits CSV per benchmark.  ``--json`` additionally writes ``BENCH_fig9.json``
(per-strategy t_select/t_capture/t_execute/t_probe/t_repair + reused-exec
means and the speedup over ``benchmarks/seed_fig9_baseline.json``),
``BENCH_maintenance.json``, ``BENCH_shard.json``, ``BENCH_admission.json``
(batched vs sequential admission, >= 3x per-query miss-path floor enforced at
quick scale), ``BENCH_chaos.json`` (>= 100 chaos-differential replay
sequences, >= 3x recovery-vs-recapture, <= 5% health-tracking tax) and
``BENCH_rpc.json`` (>= 100 cross-backend replays: real subprocess shards vs
in-process fused, <= 1.3x transport tax on warm hits, >= 3x process-kill
recovery vs cold re-capture) and ``BENCH_failover.json`` (standby takeover
>= 3x cheaper than cold rebuild + re-capture, <= 5% replication tax on warm
fused serving) so
successive PRs have a perf trajectory to compare against.  The dry-run/roofline artifacts are
produced by ``repro.launch.dryrun`` + ``benchmarks.roofline`` (they need the
512-device XLA flag and hence their own process).
"""
from __future__ import annotations

import argparse
import functools
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["quick", "full"], default="quick")
    ap.add_argument("--quick", action="store_true",
                    help="alias for --scale quick (CI smoke job)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_*.json next to the working directory")
    args = ap.parse_args()
    if args.quick:
        args.scale = "quick"

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_ablation,
        bench_admission,
        bench_chaos,
        bench_fig4_bootstrap,
        bench_fig7_strategies,
        bench_fig8_accuracy,
        bench_failover,
        bench_fig9_endtoend,
        bench_maintenance,
        bench_rpc,
        bench_shard,
        bench_table1,
    )

    benches = {
        "table1": bench_table1.run,
        "fig4": bench_fig4_bootstrap.run,
        "fig7": bench_fig7_strategies.run,
        "fig8": bench_fig8_accuracy.run,
        "fig9": functools.partial(
            bench_fig9_endtoend.run,
            json_path="BENCH_fig9.json" if args.json else None,
        ),
        "ablation": bench_ablation.run,
        "maintenance": functools.partial(
            bench_maintenance.run,
            json_path="BENCH_maintenance.json" if args.json else None,
        ),
        "shard": functools.partial(
            bench_shard.run,
            json_path="BENCH_shard.json" if args.json else None,
        ),
        "admission": functools.partial(
            bench_admission.run,
            json_path="BENCH_admission.json" if args.json else None,
        ),
        "chaos": functools.partial(
            bench_chaos.run,
            json_path="BENCH_chaos.json" if args.json else None,
        ),
        "rpc": functools.partial(
            bench_rpc.run,
            json_path="BENCH_rpc.json" if args.json else None,
        ),
        "failover": functools.partial(
            bench_failover.run,
            json_path="BENCH_failover.json" if args.json else None,
        ),
    }
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} (scale={args.scale}) ===", flush=True)
        t0 = time.time()
        try:
            fn(scale=args.scale)
            print(f"# {name}: {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
