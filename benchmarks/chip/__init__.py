"""The chip benchmark: one data-driven harness, one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a ``workloads`` entry of the repository's ``BENCHMARK.json``: a
configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``) and its per-layer metrics
(``metrics/<metric>.py``), each found by name.  Everything that measures
lives here and imports nothing of the engine except the system under test.
"""
