"""The chip benchmark: one data-driven harness, one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a ``workloads`` entry of the repository's ``BENCHMARK.json``: a
configuration (``configs/<config>.json``) whose tables a generator makes
(``generators/<name>.py``), a traffic mix (``traffic/<traffic>.json``) and
its per-layer metrics (``metrics/<metric>.py``), each found by name.
Everything that measures lives here and imports nothing of the engine
except the system under test.
"""
import importlib.util
import os


def load_module(root: str, kind: str, name: str):
    """``benchmarks/chip/<kind>/<name>.py`` of the checkout at ``root``."""
    path = os.path.join(root, "benchmarks", "chip", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
