"""PAD01 — shape hazards on hot paths (retrace bombs).

Every hot-path launch in this repo is shape-stable by construction: row
counts, fragment axes, pair counts, group axes, shard and query axes are
all quantized to size classes (powers of two, or the segment aggregate's
row and group classes in ``repro.runtime.shapes``) before they reach an
array constructor, so a steady workload stays inside a small set of
compiled size classes.  A constructor whose size derives from raw data
(``len(rows)``, ``n + 1``, a bare count) compiles a fresh XLA program per
distinct size — the retrace bombs the ``TRACE_COUNTS`` tests exist to catch
at runtime; this rule catches them at review time.

In hot-path functions (``@hot_path`` roots + call-graph closure), the size
argument of ``jnp/np.{zeros,ones,full,empty}`` must be

* a literal (or tuple of literals), or
* inherited from an existing array's ``.shape`` (no new size class), or
* routed through a size-class helper — any call whose name contains
  ``pow2``, or one of ``repro.runtime.shapes``' class functions
  (``SIZE_CLASS_HELPERS``) — directly or through one level of local
  assignment.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional

from tools.analyze.driver import Context, Finding, ModuleInfo, call_name, dotted_name

RULE = "PAD01"

CONSTRUCTORS = {"zeros", "ones", "full", "empty"}
# ``repro.runtime.shapes``' size-class functions, by name.
SIZE_CLASS_HELPERS = {"row_class", "group_class"}


def _is_constant_shape(expr: ast.AST) -> bool:
    if isinstance(expr, ast.Constant):
        return True
    if isinstance(expr, (ast.Tuple, ast.List)):
        return all(_is_constant_shape(e) for e in expr.elts)
    if isinstance(expr, ast.UnaryOp):
        return _is_constant_shape(expr.operand)
    return False


def _has_size_class_marker(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            name = call_name(sub)
            if name is not None and ("pow2" in name.lower()
                                     or name.split(".")[-1] in SIZE_CLASS_HELPERS):
                return True
    return False


def _is_shape_inherited(expr: ast.AST) -> bool:
    """``x.shape`` / ``x.shape[0]`` / ``x.size`` reuse an existing array's
    size class — no new compilation.  ``num_rows`` / ``num_samples`` are the
    repo's ColumnTable/SampleSet row-count properties: they mirror the
    backing arrays' leading dim (padded to their row class upstream for
    sketch instances), so a constructor sized to them inherits an existing
    class too."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Attribute) and sub.attr in (
                "shape", "size", "num_rows", "num_samples"):
            return True
    return False


def _local_assignments(fn_node: ast.AST) -> Dict[str, List[ast.AST]]:
    out: Dict[str, List[ast.AST]] = {}
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                if isinstance(t, ast.Name):
                    out.setdefault(t.id, []).append(sub.value)
        elif isinstance(sub, ast.AnnAssign) and sub.value is not None:
            if isinstance(sub.target, ast.Name):
                out.setdefault(sub.target.id, []).append(sub.value)
    return out


def _shape_ok(expr: ast.AST, assigns: Dict[str, List[ast.AST]]) -> bool:
    if _is_constant_shape(expr) or _has_size_class_marker(expr) or _is_shape_inherited(expr):
        return True
    # Resolve names one level through local assignments: a size computed as
    # ``n_pad = _next_pow2(n)`` then used as ``jnp.zeros(n_pad)`` is fine.
    names = [s.id for s in ast.walk(expr) if isinstance(s, ast.Name)]
    if not names:
        return False
    for name in names:
        exprs = assigns.get(name)
        if not exprs:
            return False  # parameter or outer value: unknown provenance
        if not all(_is_constant_shape(e) or _has_size_class_marker(e)
                   or _is_shape_inherited(e) for e in exprs):
            return False
    return True


def check(module: ModuleInfo, ctx: Context) -> List[Finding]:
    out: List[Finding] = []
    for fn in module.functions:
        if not (fn.is_hot_root or ctx.is_hot(module, fn)):
            continue
        assigns = _local_assignments(fn.node)
        for sub in ast.walk(fn.node):
            if not isinstance(sub, ast.Call):
                continue
            name = call_name(sub)
            if name is None:
                continue
            parts = name.split(".")
            if parts[-1] not in CONSTRUCTORS or len(parts) < 2:
                continue
            # Host numpy constructors don't compile anything; only device
            # (jnp/jax) constructors mint XLA size classes.
            if parts[0] not in ("jnp", "jax"):
                continue
            if not sub.args:
                continue
            shape = sub.args[0]
            if parts[-1] == "full" and len(sub.args) >= 2:
                pass  # first arg is still the shape
            if not _shape_ok(shape, assigns):
                out.append(Finding(
                    RULE, module.path, sub.lineno,
                    f"hot-path {name}(...) with a data-dependent size that "
                    f"bypasses the size-class helpers — every distinct size "
                    f"compiles a fresh XLA program"))
    return out
