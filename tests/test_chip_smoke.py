"""``chip_smoke.py`` keeps working between chip runs.

Its ``--rehearse`` mode runs every phase on the CPU at a tiny size with
interpret-mode kernels; the script must pass that, must never claim ``ok``
without a TPU, and must fail when the rest of the repository is absent.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(args[0]), *args[1:]], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_rehearsal_passes():
    proc = _run([SCRIPT, "--rehearse"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["rehearsal"] == "passed" and "ok" not in last
    phases = [json.loads(line) for line in lines[1:-1]]
    assert {p["phase"] for p in phases} >= {
        "exactness", "cold_agh", "warm_agh", "batch_cold", "batch_warm",
        "mutate_repair", "sharded_hits", "join_ajgh_cold", "kernel_dispatch"}
    assert all(p.get("ok", True) for p in phases)
    for p in phases:
        if p["phase"] in ("warm_agh", "batch_warm", "join_ajgh_warm"):
            assert p["compiles"] == 0, p


def test_chip_smoke_without_tpu_exits_nonzero():
    proc = _run([SCRIPT], timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_chip_smoke_alone_exits_nonzero(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    proc = _run([tmp_path / SCRIPT.name], cwd=tmp_path, timeout=60)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
