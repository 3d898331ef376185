"""Tests for the runtime sanitizer layer (``repro.runtime.guards``) and the
process-stable hashing behind ``PBDSEngine._select_key``."""
import collections
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.guards import (
    HOT_PATHS,
    LaunchCountError,
    RetraceError,
    hot_path,
    launch_guard,
    retrace_guard,
    sanitize_enabled,
    sanitized,
    transfer_guard,
)
from repro.runtime.stable_hash import canonical_repr, stable_hash32

REPO = Path(__file__).resolve().parent.parent


# -- retrace guard ----------------------------------------------------------


@jax.jit
def _double(x):
    return x * 2.0


def test_retrace_guard_passes_on_cached_execution():
    _double(jnp.ones(8))  # warm
    with retrace_guard(allowed=0):
        _double(jnp.ones(8))
        _double(jnp.zeros(8))  # same shape: same executable


def test_retrace_guard_raises_on_fresh_compile():
    _double(jnp.ones(8))  # warm the 8-class
    with pytest.raises(RetraceError, match="size class"):
        with retrace_guard(allowed=0, label="double"):
            _double(jnp.ones(16))  # new size class: real backend compile


def test_retrace_guard_observe_mode_counts():
    with retrace_guard(allowed=None) as watch:
        _double(jnp.ones(32))  # cold
    assert watch.compiles >= 1
    with retrace_guard(allowed=None) as watch:
        _double(jnp.ones(32))  # warm
    assert watch.compiles == 0


def test_retrace_guard_names_the_compiled_functions():
    x = jnp.ones(24)
    with pytest.raises(RetraceError, match=r"jit\(_double\)"):
        with retrace_guard(allowed=0) as watch:
            _double(x)  # new size class: real backend compile
    assert watch.events == ["jit(_double)"]


def test_retrace_guard_unregisters_when_block_raises():
    """A block that raises leaves no listener behind: later compiles are
    not counted into its watch."""
    with pytest.raises(KeyError):
        with retrace_guard(allowed=None) as watch:
            raise KeyError("boom")
    _double(jnp.ones(48))  # a fresh compile after the block
    assert watch.compiles == 0


# -- launch guard -----------------------------------------------------------


def test_launch_guard_expect():
    counter = collections.Counter()
    with launch_guard("probe", expect=2, counter=counter):
        counter["probe"] += 1
        counter["probe"] += 1
    with pytest.raises(LaunchCountError, match="expected 1"):
        with launch_guard("probe", expect=1, counter=counter):
            counter["probe"] += 2


def test_launch_guard_observe():
    counter = collections.Counter(probe=5)
    with launch_guard("probe", counter=counter) as watch:
        counter["probe"] += 3
    assert watch.launches == 3


# -- hot_path ----------------------------------------------------------------


def test_hot_path_is_free_and_registers():
    @hot_path
    def serve(x):
        return x

    assert serve.__hot_path__ is True
    assert serve(41) == 41  # no wrapper
    assert any(name.endswith("serve") for name in HOT_PATHS)


def test_engine_entry_points_are_tagged():
    from repro.core.engine import PBDSEngine
    from repro.core.shard import ShardedEngine

    assert PBDSEngine.run.__hot_path__
    assert PBDSEngine.run_batch.__hot_path__
    assert ShardedEngine.run.__hot_path__
    assert ShardedEngine.run_batch.__hot_path__


# -- sanitized() gating ------------------------------------------------------


def test_sanitized_noop_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    assert not sanitize_enabled()
    with sanitized(allowed_compiles=0) as watch:
        assert watch is None
        _double(jnp.ones((3, 7)))  # fresh compile: no-op guard stays silent


def test_sanitized_armed_when_enabled(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert sanitize_enabled()
    _double(jnp.ones(8))  # warm
    # leaks=False: jax.checking_leaks uses a fresh trace context, which
    # defeats the executable cache and would count as compiles here.
    with sanitized(allowed_compiles=0, transfer=None, leaks=False) as watch:
        _double(jnp.ones(8))
    assert watch is not None and watch.compiles == 0
    with pytest.raises(RetraceError):
        with sanitized(allowed_compiles=0, transfer=None, leaks=False):
            _double(jnp.ones((2, 2, 2)))


def test_transfer_guard_composes():
    # On CPU host==device so "disallow" cannot trip; this pins that the
    # wrapper at least routes through jax.transfer_guard without breaking
    # device code paths.
    with transfer_guard("log"):
        jnp.arange(4).sum()


# -- stable hashing ----------------------------------------------------------


def test_canonical_repr_matches_repr_for_plain_signatures():
    sig = ("tpch", ("a", "b"), ("sum", "x"), None, (">", 1.5), None)
    assert canonical_repr(sig) == repr(sig)
    assert canonical_repr((1,)) == repr((1,))  # 1-tuple trailing comma


def test_canonical_repr_normalizes_np_scalars_and_sets():
    assert canonical_repr(np.float32(1.5)) == canonical_repr(1.5)
    assert canonical_repr(np.int64(7)) == canonical_repr(7)
    assert canonical_repr({"b", "a"}) == canonical_repr({"a", "b"})
    assert canonical_repr({"k": 1, "j": 2}) == canonical_repr({"j": 2, "k": 1})


def test_canonical_repr_rejects_unknown_types():
    with pytest.raises(TypeError):
        canonical_repr(object())


def test_stable_hash32_range():
    h = stable_hash32(("t", (">", 3.0)))
    assert 0 <= h <= 0x7FFFFFFF


_HASH_SCRIPT = textwrap.dedent("""
    from repro.core.queries import Aggregate, Having, Predicate, Query
    from repro.runtime.stable_hash import stable_hash32

    q = Query("tpch", ("region", "nation"), Aggregate("sum", "rev"),
              where=Predicate("qty", ">", 30.0), having=Having(">=", 100.0))
    print(stable_hash32(q.signature()))
    print(stable_hash32(("mixed", frozenset({"b", "a"}), {"z": 1, "y": 2})))
""")


def test_select_key_hash_stable_across_processes():
    """The shard-routing hash must not depend on PYTHONHASHSEED, interning,
    or numpy repr quirks: two processes with different hash seeds must agree
    (distributed routers disagreeing would double-serve / drop queries)."""
    outs = []
    for seed in ("0", "4242"):
        proc = subprocess.run(
            [sys.executable, "-c", _HASH_SCRIPT],
            capture_output=True, text=True, cwd=REPO,
            env={"PYTHONPATH": str(REPO / "src"), "PYTHONHASHSEED": seed,
                 "JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin:/usr/local/bin"})
        assert proc.returncode == 0, proc.stderr
        outs.append(proc.stdout)
    assert outs[0] == outs[1]


def test_select_key_uses_stable_hash():
    """Engine selection keys derive via stable_hash32, not builtin hash."""
    from repro.core.datasets import make_tpch
    from repro.core.engine import PBDSEngine
    from repro.core.queries import Aggregate, Having, Query

    db = make_tpch(2_000, seed=3)
    eng = PBDSEngine(db)
    q = Query("orders", ("o_orderpriority",), Aggregate("count"),
              having=Having(">", 5.0))
    expected = jax.random.fold_in(eng._base_key, stable_hash32(q.signature()))
    assert np.array_equal(np.asarray(eng._select_key(q)), np.asarray(expected))
