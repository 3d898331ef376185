#!/usr/bin/env python3
"""The lower-precision control: the reference in the engine's place, with
the aggregated column rounded to bfloat16, held to a cell's checks.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

The configurations state float32 aggregation; bfloat16 is the step below,
and the one a later change would be tempted by (the aggregation is
bytes-bound, and a bfloat16 value column halves the bytes).  For each seed
it builds the cell's tables and stream as a run does (the window at the
cell's rate and ``run_seconds``), answers every set-up, warm-up and window
query from the bfloat16 reference, and prints one JSON line with the
numbers the check compares and their limits.  A limit is sound only where
the control fails it.  Pure numpy: it needs no chip.
"""
import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import datagen, harness, reference, traffic  # noqa: E402


@dataclasses.dataclass
class Answer:
    group_values: dict
    values: object


def readings(cell: harness.Cell, seed: int, seconds: float) -> dict:
    import ml_dtypes

    gen = datagen.generate(cell.config, seed, root=cell.root)
    tables = {k: cols for k, (cols, _) in gen.items()}
    stream = traffic.build(cell.traffic, tables, seed, seconds)
    low = [reference.exact_groups(t, tables, value_dtype=ml_dtypes.bfloat16)
           for t in stream.templates]
    asked = stream.setup + stream.warmup + stream.queries
    checked = [(j, thr, Answer(*reference.answer_from_reference(
        low[j], stream.templates[j].groupby, stream.op, thr))) for j, thr in asked]
    checks, _ = harness.check(stream, checked, cell.traffic["checks"], 0)
    return {"workload": cell.name, "seed": seed, "answers": len(checked),
            "checks": checks,
            "fails": any(c["value"] > c["limit"] for c in checks.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    cell = harness.load_cell(harness.ROOT, args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
