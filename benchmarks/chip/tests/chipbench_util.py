"""Shared helpers of the benchmark's tests: put the checkout and its
``src`` on the path, and run one rehearsal in this process."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def rehearse(capsys, workload, seed=11, seconds=1, trace=0, root=ROOT):
    """Run one cell's rehearsal; return (exit code, last stdout line or None)."""
    from benchmarks.chip import harness

    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace), "--rehearse"], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
