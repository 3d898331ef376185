"""Unit coverage for the runtime resilience/elastic primitives.

These are the building blocks the chaos-tolerant serving layer composes:
``with_retries`` wraps every shard op, ``StragglerMonitor`` feeds shard
health, ``plan_remesh``/``feasible_mesh_shape`` and ``plan_replacement``
are the pure re-planning policies (device meshes and fragment placement
respectively).  All are deterministic and tested without any engine.
"""
import time

import numpy as np
import pytest

from repro.runtime import (
    RetryPolicy,
    StragglerMonitor,
    feasible_mesh_shape,
    plan_remesh,
    plan_replacement,
    with_retries,
)


class _Boom(RuntimeError):
    pass


class _Fatal(ValueError):
    pass


def _failing(n_failures, exc=_Boom):
    """A callable that raises ``exc`` for the first ``n_failures`` calls."""
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] <= n_failures:
            raise exc(f"fail {calls['n']}")
        return calls["n"]

    fn.calls = calls
    return fn


def test_with_retries_backoff_sequencing(monkeypatch):
    sleeps = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    fn = _failing(2)
    policy = RetryPolicy(max_attempts=4, backoff_s=0.1, backoff_mult=3.0,
                         retryable=(_Boom,), jitter=0.0)
    assert with_retries(fn, policy) == 3
    # One sleep per retry, geometric: 0.1 then 0.3 (jitter disabled).
    assert sleeps == pytest.approx([0.1, 0.3])
    assert fn.calls["n"] == 3


def test_with_retries_jitter_decorrelates_and_is_seeded(monkeypatch):
    def run(seed):
        sleeps = []
        monkeypatch.setattr(time, "sleep", sleeps.append)
        policy = RetryPolicy(max_attempts=6, backoff_s=0.1, backoff_mult=3.0,
                             retryable=(_Boom,), jitter=0.5, seed=seed)
        with pytest.raises(_Boom):
            with_retries(_failing(10), policy)
        return sleeps

    a, b, a2 = run(1), run(2), run(1)
    # Seeded: the same seed replays the same sleeps; different seeds (two
    # clients retrying against the same recovering shard) decorrelate.
    assert a == pytest.approx(a2)
    assert a != pytest.approx(b)
    # Every sleep stays within the decorrelated-jitter envelope:
    # [backoff_s, prev * mult * (1 + jitter)).
    prev = 0.1 / 3.0
    for s in a:
        assert 0.1 <= s < prev * 3.0 * 1.5 + 1e-12
        prev = s


def test_with_retries_sleep_capped_to_deadline(monkeypatch):
    sleeps = []
    clock = {"t": 0.0}
    monkeypatch.setattr(time, "perf_counter", lambda: clock["t"])

    def fake_sleep(s):
        sleeps.append(s)
        clock["t"] += s

    monkeypatch.setattr(time, "sleep", fake_sleep)
    fn = _failing(10)
    policy = RetryPolicy(max_attempts=50, backoff_s=10.0, backoff_mult=2.0,
                         retryable=(_Boom,), deadline_s=1.0, jitter=0.0)
    with pytest.raises(_Boom):
        with_retries(fn, policy)
    # The first sleep would be 10s; the cap trims it to the remaining 1s
    # budget, and the next failure hits the exhausted deadline: the loop
    # never sleeps past deadline_s.
    assert sleeps == pytest.approx([1.0])
    assert sum(sleeps) <= policy.deadline_s + 1e-9
    assert fn.calls["n"] == 2


def test_with_retries_on_retry_and_exhaustion(monkeypatch):
    monkeypatch.setattr(time, "sleep", lambda _s: None)
    seen = []
    fn = _failing(10)
    policy = RetryPolicy(max_attempts=3, backoff_s=0.01, retryable=(_Boom,))
    with pytest.raises(_Boom):
        with_retries(fn, policy, on_retry=lambda a, e: seen.append((a, str(e))))
    # on_retry fires for every attempt EXCEPT the last (which re-raises).
    assert seen == [(1, "fail 1"), (2, "fail 2")]
    assert fn.calls["n"] == 3


def test_with_retries_non_retryable_passthrough(monkeypatch):
    sleeps = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    fn = _failing(1, exc=_Fatal)
    policy = RetryPolicy(max_attempts=5, retryable=(_Boom,))
    with pytest.raises(_Fatal):
        with_retries(fn, policy)
    # No retries, no sleeps: a non-retryable error surfaces immediately.
    assert fn.calls["n"] == 1
    assert sleeps == []


def test_with_retries_deadline_stops_early(monkeypatch):
    monkeypatch.setattr(time, "sleep", lambda _s: None)
    fn = _failing(10)
    policy = RetryPolicy(max_attempts=50, backoff_s=0.0,
                         retryable=(_Boom,), deadline_s=0.0)
    with pytest.raises(_Boom):
        with_retries(fn, policy)
    # Deadline already expired at the first failure: exactly one attempt.
    assert fn.calls["n"] == 1


def test_straggler_monitor_warmup_and_flagging():
    mon = StragglerMonitor(window=32, threshold=2.0)
    # Below max(4, window // 4) = 8 observations there is no baseline.
    for _ in range(7):
        assert mon.median() is None
        assert mon.observe(0.01) is False
    assert mon.median() is None  # 7 observed, still warming up
    assert mon.observe(0.01) is False  # 8th observation forms the baseline
    assert mon.median() == pytest.approx(0.01)
    assert mon.observe(0.019) is False  # under 2x median: not a straggler
    assert mon.observe(0.05) is True    # over 2x median: flagged
    assert mon.flagged == 1
    assert mon.observe(0.5) is True
    assert mon.flagged == 2


def test_straggler_monitor_small_window_floor():
    # window // 4 < 4: the warmup floor is 4 observations.
    mon = StragglerMonitor(window=8, threshold=2.0)
    for _ in range(3):
        mon.observe(1.0)
    assert mon.median() is None
    mon.observe(1.0)
    assert mon.median() == pytest.approx(1.0)


def test_feasible_mesh_shape_invariants():
    assert feasible_mesh_shape(8, 2) == (4, 2)
    assert feasible_mesh_shape(7, 2) == (3, 2)  # drops the odd device
    assert feasible_mesh_shape(1, 2) is None    # cannot fit TP extent
    assert feasible_mesh_shape(8, 2, prefer_pods=2) == (2, 2, 2)
    # Pod preference degrades gracefully when it doesn't divide.
    assert feasible_mesh_shape(6, 2, prefer_pods=2) == (3, 2)


@pytest.mark.parametrize("n_devices", [8, 7, 6, 5, 4])
def test_plan_remesh_preserves_global_batch(n_devices):
    global_batch, model_parallel = 32, 2
    plan = plan_remesh(n_devices, model_parallel, global_batch,
                       old_n_micro=2, old_data_extent=4)
    assert plan is not None
    data_extent = plan.mesh_shape[-2] * (
        plan.mesh_shape[0] if len(plan.mesh_shape) == 3 else 1)
    # Global batch is always preserved exactly through grad accumulation.
    assert global_batch % plan.n_micro == 0
    # And splits evenly across the DP extent whenever that is achievable
    # (a coprime extent, e.g. 3 devices for batch 32, cannot).
    if global_batch % data_extent == 0:
        assert (global_batch // plan.n_micro) % data_extent == 0
    used = int(np.prod(plan.mesh_shape))
    assert used + plan.dropped_devices == n_devices


def test_plan_replacement_invariants():
    sizes = np.array([10, 30, 20, 40, 10, 25])
    owner = np.array([0, 0, 1, 1, 2, 2])
    new = plan_replacement(sizes, owner, 3, dead=[1])
    # Survivors keep every fragment they already owned.
    assert (new[owner == 0] == 0).all()
    assert (new[owner == 2] == 2).all()
    # Orphans all land on survivors.
    assert set(new[owner == 1].tolist()) <= {0, 2}
    # Greedy LPT: the 40-row orphan goes to the lighter survivor (shard 2:
    # 35 rows vs shard 0: 40), then the 20-row one to the other.
    assert new[3] == 2 and new[2] == 0
    # Deterministic and pure.
    assert np.array_equal(new, plan_replacement(sizes, owner, 3, dead=[1]))
    assert np.array_equal(owner, [0, 0, 1, 1, 2, 2])  # input untouched


def test_plan_replacement_no_survivors():
    with pytest.raises(ValueError):
        plan_replacement(np.array([1.0]), np.array([0]), 2, dead=[0, 1])


# -- process boundaries: compile cache placement, child environments --------

_CACHE_PROBE = (
    "import jax\n"
    "from repro.runtime.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_follows_env_else_fixed_checkout_path(tmp_path, from_env):
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    env = {"PYTHONPATH": str(repo / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    want = str(repo / ".jax_cache")
    if from_env:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]


def test_child_env_pins_children_to_the_cpu(monkeypatch):
    """Spawned shard servers and standbys never try to take the chip."""
    import os

    from repro.core.shard_rpc import child_env

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert os.path.isdir(os.path.join(env["PYTHONPATH"].split(os.pathsep)[0], "repro"))
