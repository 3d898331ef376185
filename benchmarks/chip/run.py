#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the set-up split, the window's counters and each compared number with
its limit on standard error, and as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks``.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result; ``--rehearse`` (tests only) runs on the CPU at a tiny size.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
