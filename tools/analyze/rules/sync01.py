"""SYNC01 — host-device synchronization on hot paths.

``.item()``, ``float(x)`` / ``int(x)`` and ``np.asarray(x)`` on a device
value block the host on the device stream.  One sync at a deliberate merge
point is a design decision (and gets a waiver saying so); a sync smeared
into a per-item loop or a function that runs per query is the difference
between the fused one-launch hot path and the host-loop it replaced.

Scope: functions in the hot-path closure (``@hot_path`` roots + the
call-graph walk from them, matched by simple name across the fileset).

Device-derived values are tracked per function: a local assigned from a
``jnp.*`` / ``jax.*`` call (or from another device-derived local) is
device-derived; flagged sync forms are

* ``<anything>.item()`` — always a sync;
* ``float(e)`` / ``int(e)`` where ``e`` mentions a device-derived value;
* ``np.asarray(e)`` / ``np.array(e)`` likewise;
* ``to_host(e)`` (``repro.runtime.trace``) — always a sync: it exists to
  copy a device value to the host.
"""
from __future__ import annotations

import ast
from typing import List, Set

from tools.analyze.driver import Context, Finding, ModuleInfo, call_name

RULE = "SYNC01"

SYNC_BUILTINS = {"float", "int"}
SYNC_NP = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
SYNC_TO_HOST = {"to_host", "trace.to_host"}


def _device_rooted_call(call: ast.Call) -> bool:
    name = call_name(call)
    return name is not None and name.split(".", 1)[0] in ("jnp", "jax")


def _is_host_copy(value: ast.AST) -> bool:
    """``np.asarray(...)`` (or a tuple of them) materializes HOST copies:
    the transfer is flagged at that line; downstream float()/int() on the
    bound names are free."""
    if isinstance(value, ast.Call):
        name = call_name(value)
        return name in SYNC_NP or name in SYNC_TO_HOST
    if isinstance(value, (ast.Tuple, ast.List)) and value.elts:
        return all(_is_host_copy(e) for e in value.elts)
    return False


def _device_locals(fn_node: ast.AST) -> Set[str]:
    """Two ordered passes over assignments: a value mentioning a jnp/jax
    call (or a device-derived name) marks its targets device; re-binding a
    name to a host copy un-marks it.  The second pass covers loop-carried
    flows; the result approximates the state at the *last* binding, which is
    what the sync checks below care about."""
    assigns = []
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Assign):
            assigns.append((sub.lineno, sub.targets, sub.value))
        elif isinstance(sub, ast.AnnAssign) and sub.value is not None:
            assigns.append((sub.lineno, [sub.target], sub.value))
    assigns.sort(key=lambda a: a[0])
    device: Set[str] = set()
    for _ in range(2):
        for _, targets, value in assigns:
            if _is_host_copy(value):
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            device.discard(n.id)
                continue
            mentions_device = any(
                (isinstance(s, ast.Call) and _device_rooted_call(s))
                or (isinstance(s, ast.Name) and s.id in device)
                for s in ast.walk(value))
            if not mentions_device:
                continue
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        device.add(n.id)
    return device


def _is_static_metadata(expr: ast.AST) -> bool:
    """``int(x.shape[0])`` / ``float(x.ndim)`` read static trace-time
    metadata, not device data — no sync."""
    return any(isinstance(s, ast.Attribute) and s.attr in ("shape", "ndim")
               for s in ast.walk(expr))


def _mentions_device(expr: ast.AST, device: Set[str]) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Name) and sub.id in device:
            return True
        if isinstance(sub, ast.Call) and _device_rooted_call(sub):
            return True
    return False


def check(module: ModuleInfo, ctx: Context) -> List[Finding]:
    out: List[Finding] = []
    for fn in module.functions:
        if not (fn.is_hot_root or ctx.is_hot(module, fn)):
            continue
        device = _device_locals(fn.node)
        for sub in ast.walk(fn.node):
            if not isinstance(sub, ast.Call):
                continue
            name = call_name(sub)
            if isinstance(sub.func, ast.Attribute) and sub.func.attr == "item" \
                    and not sub.args \
                    and _mentions_device(sub.func.value, device):
                out.append(Finding(
                    RULE, module.path, sub.lineno,
                    "hot-path .item() forces a device->host sync"))
                continue
            if name in SYNC_TO_HOST:
                out.append(Finding(
                    RULE, module.path, sub.lineno,
                    "hot-path to_host() copies a device value to the host"))
                continue
            if name in SYNC_BUILTINS and len(sub.args) == 1 \
                    and not _is_static_metadata(sub.args[0]) \
                    and _mentions_device(sub.args[0], device):
                out.append(Finding(
                    RULE, module.path, sub.lineno,
                    f"hot-path {name}() on a device value forces a "
                    f"device->host sync"))
                continue
            if name in SYNC_NP and sub.args \
                    and _mentions_device(sub.args[0], device):
                out.append(Finding(
                    RULE, module.path, sub.lineno,
                    f"hot-path {name}() on a device value forces a "
                    f"device->host transfer"))
    return out
