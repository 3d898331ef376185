"""Runtime sanitizer layer: the invariants the hot path rests on, enforced.

Every fast path this repo ships rests on invariants the type system cannot
see: shapes padded to size classes (``runtime/shapes.py``, powers of two
elsewhere) so steady-state reuse compiles zero XLA programs, PRNG keys
never reused across passes, one launch per served hit batch, device values
never synced mid-loop.  ``tools.analyze`` checks what a static
pass can see at review time; this module is the *runtime* half — shared
telemetry counters and guard context managers that turn "should never
happen" into a raised error in tests.

Telemetry
---------
``TRACE_COUNTS``
    Bumped inside jitted bodies, so the count moves at *trace* time only.
    Tests assert pow2 quantization keeps shape drift inside one compiled
    size class (``core/shard._fused_body``, ``aqp/size_estimation``'s
    incidence pass both count here).
``LAUNCH_COUNTS``
    Bumped once per host-side invocation of a fused launch; tests assert
    the hit path costs exactly one launch per batch.

Guards (each usable standalone; ``sanitized()`` composes them and is a
no-op unless ``REPRO_SANITIZE=1``):
``retrace_guard(allowed=0)``
    Counts real XLA backend compilations inside the block (cached
    executions emit no event) and raises :class:`RetraceError` when more
    than ``allowed`` happen — the shared replacement for the ad-hoc
    compile-listener fixtures the admission/shard/catalog suites grew.
``launch_guard(name, expect=n)``
    Asserts exactly ``n`` host-side launches of counter ``name`` ran.
``transfer_guard(level)``
    Thin wrapper over ``jax.transfer_guard`` — ``"disallow"`` inside a
    device-only region turns a silent host sync into an error.
``tracer_leak_guard()``
    ``jax.checking_leaks()`` — a traced value escaping its trace (the bug
    class behind stale-closure retrace bombs) raises instead of leaking.

``@hot_path`` marks serving-critical entry points.  It is free at runtime
(tags the function and records its qualname); its real consumer is
``tools.analyze``, whose SYNC01/PAD01 rules walk the call graph from the
decorated roots.
"""
from __future__ import annotations

import collections
import contextlib
import os
from typing import Callable, Iterator, List, Optional, TypeVar

F = TypeVar("F", bound=Callable)

# Shared telemetry: one namespace for every hot-path counter (keys are
# owned by the bumping module, e.g. "fused_partials", "incidence_pass").
TRACE_COUNTS: collections.Counter = collections.Counter()
LAUNCH_COUNTS: collections.Counter = collections.Counter()

# Qualified names registered by @hot_path, in registration order.
HOT_PATHS: List[str] = []


def hot_path(fn: F) -> F:
    """Mark ``fn`` as a serving-critical hot path.

    Zero runtime cost (no wrapper): sets ``__hot_path__`` and records the
    qualified name so tooling — and humans reading the code — know the
    function is subject to the hot-path invariants (no host-device sync,
    shapes padded to size classes, no per-call retraces).  ``tools.analyze``
    discovers the decorator syntactically, so decorating never imports the
    analyzer.
    """
    HOT_PATHS.append(f"{fn.__module__}.{fn.__qualname__}")
    fn.__hot_path__ = True  # type: ignore[attr-defined]
    return fn


class GuardViolation(AssertionError):
    """A runtime sanitizer guard tripped."""


class RetraceError(GuardViolation):
    """More XLA backend compilations than the guarded block allows."""


class LaunchCountError(GuardViolation):
    """A guarded block launched a different number of times than expected."""


class CompileWatch:
    """Live view of backend compilations inside a ``retrace_guard`` block:
    ``events`` holds the compiled function's name for each."""

    def __init__(self) -> None:
        self.events: List[str] = []

    @property
    def compiles(self) -> int:
        return len(self.events)


@contextlib.contextmanager
def retrace_guard(allowed: Optional[int] = 0, label: str = "") -> Iterator[CompileWatch]:
    """Fail when the block compiles more than ``allowed`` XLA programs.

    ``allowed=None`` only observes (use the yielded :class:`CompileWatch`
    to assert that warmup *did* compile).  Counts real backend
    compilations — tracing that hits the executable cache emits no event —
    which is exactly the "steady state compiles nothing new" contract the
    size-class padding exists to uphold.
    """
    from jax import monitoring

    watch = CompileWatch()

    def listener(name: str, duration_secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            watch.events.append(str(kw.get("fun_name", "?")))

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield watch
    finally:
        monitoring.unregister_event_duration_listener(listener)
    if allowed is not None and watch.compiles > allowed:
        where = f" [{label}]" if label else ""
        raise RetraceError(
            f"retrace_guard{where}: {watch.compiles} XLA compilation(s) "
            f"({', '.join(watch.events)}), allowed {allowed} — a hot path "
            f"left its compiled size class")


class LaunchWatch:
    """Live view of one counter's delta inside a ``launch_guard`` block."""

    def __init__(self, counter: collections.Counter, name: str) -> None:
        self._counter = counter
        self._name = name
        self._before = counter[name]

    @property
    def launches(self) -> int:
        return self._counter[self._name] - self._before


@contextlib.contextmanager
def launch_guard(
    name: str,
    expect: Optional[int] = None,
    counter: Optional[collections.Counter] = None,
) -> Iterator[LaunchWatch]:
    """Watch ``LAUNCH_COUNTS[name]`` over the block; with ``expect`` set,
    fail unless exactly that many launches ran (the "one launch per served
    batch" contract)."""
    watch = LaunchWatch(LAUNCH_COUNTS if counter is None else counter, name)
    yield watch
    if expect is not None and watch.launches != expect:
        raise LaunchCountError(
            f"launch_guard[{name}]: {watch.launches} launch(es), expected {expect}")


@contextlib.contextmanager
def transfer_guard(level: str = "disallow") -> Iterator[None]:
    """``jax.transfer_guard`` over the block: ``"disallow"`` makes any
    implicit host<->device transfer (``float(x)``, ``np.asarray(x)`` on a
    traced/device value) raise instead of silently syncing."""
    import jax

    with jax.transfer_guard(level):
        yield


@contextlib.contextmanager
def tracer_leak_guard() -> Iterator[None]:
    """``jax.checking_leaks()`` over the block: a tracer escaping its trace
    raises at the leak site instead of detonating at next use."""
    import jax

    with jax.checking_leaks():
        yield


def sanitize_enabled() -> bool:
    """True when the sanitizer-enabled test mode is on (``REPRO_SANITIZE=1``)."""
    return os.environ.get("REPRO_SANITIZE", "") not in ("", "0")


@contextlib.contextmanager
def sanitized(
    allowed_compiles: Optional[int] = None,
    transfer: Optional[str] = "disallow",
    leaks: bool = True,
    label: str = "",
) -> Iterator[Optional[CompileWatch]]:
    """The combined sanitizer for device-only regions of tests.

    No-op unless ``REPRO_SANITIZE=1`` (the CI static-analysis job sets it),
    so the guarded suites run everywhere and get teeth in sanitizer mode:
    tracer-leak checking, an implicit-transfer guard, and (when
    ``allowed_compiles`` is not None) a retrace guard.
    """
    if not sanitize_enabled():
        yield None
        return
    with contextlib.ExitStack() as stack:
        if leaks:
            stack.enter_context(tracer_leak_guard())
        if transfer is not None:
            stack.enter_context(transfer_guard(transfer))
        watch = None
        if allowed_compiles is not None:
            watch = stack.enter_context(retrace_guard(allowed_compiles, label=label))
        yield watch
