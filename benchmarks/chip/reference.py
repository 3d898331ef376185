"""The plain reference: exact answers from the generated numpy columns.

Shares no code with the engine.  Group keys come from ``np.unique`` and
aggregates from float64 ``np.bincount`` (the approach of the repository's
``chip_smoke.py`` reference, which this copies).  ``value_dtype`` rounds the
aggregated column first: the lower-precision control
(``ml_dtypes.bfloat16``) uses it, the reference itself never does.  A
join template's inner block is an inner equi-join made here with
``searchsorted`` on the unique right key (``joined``).

``compare`` holds one engine answer against the exact groups of its
template: group membership (HAVING) and each returned value.  Groups whose
exact aggregate lies within the relative limit of the threshold may fall on
either side of it (float32 accumulation), and only those.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import numpy as np

_CMP = {">": np.greater, ">=": np.greater_equal, "<": np.less,
        "<=": np.less_equal, "=": np.equal}


@dataclasses.dataclass
class ExactGroups:
    """Every present group of one template: key tuples and float64 values."""

    keys: list  # tuple of float group-by values per group
    values: np.ndarray

    def index(self) -> Dict[tuple, int]:
        return {k: i for i, k in enumerate(self.keys)}


def joined(fact: Dict[str, np.ndarray], right: Dict[str, np.ndarray],
           left_key: str, right_key: str) -> Dict[str, np.ndarray]:
    """Inner equi-join ``fact.left_key = right.right_key``: each fact row
    with its one partner's columns; fact rows with no partner are dropped.
    The right key must be unique and no column name may be on both sides."""
    shared = sorted(set(fact) & set(right))
    if shared:
        raise ValueError(f"columns on both sides of the join: {shared}")
    rk = right[right_key]
    if len(np.unique(rk)) != len(rk):
        raise ValueError(f"the join's right key {right_key!r} is not unique")
    order = np.argsort(rk, kind="stable")
    pos = np.minimum(np.searchsorted(rk[order], fact[left_key]), len(rk) - 1)
    partner = order[pos]
    hit = np.nonzero(rk[partner] == fact[left_key])[0]
    out = {a: v[hit] for a, v in fact.items()}
    out.update({a: v[partner[hit]] for a, v in right.items()})
    return out


def exact_groups(template, tables: Dict[str, Dict[str, np.ndarray]],
                 value_dtype=None) -> ExactGroups:
    """Aggregate of every present group of ``template``'s inner block: its
    table, joined first where the template has a ``join``."""
    cols = tables[template.table]
    if template.join is not None:
        j = template.join
        cols = joined(cols, tables[j.right], j.left_key, j.right_key)
    n = len(cols[template.groupby[0]])
    codes, uniques = [], []
    for a in template.groupby:
        u, inv = np.unique(cols[a], return_inverse=True)
        codes.append(inv)
        uniques.append(u)
    flat = np.ravel_multi_index(codes, [len(u) for u in uniques])
    keys, gid = np.unique(flat, return_inverse=True)
    if template.agg_attr is None:
        vals = np.ones(n)
    else:
        vals = cols[template.agg_attr]
        if value_dtype is not None:
            vals = vals.astype(value_dtype)
        vals = vals.astype(np.float64)
    sums = np.bincount(gid, weights=vals, minlength=len(keys))
    counts = np.bincount(gid, minlength=len(keys)).astype(np.float64)
    agg = {"sum": sums, "count": counts,
           "avg": sums / np.maximum(counts, 1.0)}[template.agg_fn]
    per_attr = np.unravel_index(keys, [len(u) for u in uniques])
    key_vals = [u[i].astype(np.float64).tolist() for u, i in zip(uniques, per_attr)]
    return ExactGroups(keys=list(zip(*key_vals)), values=agg)


@dataclasses.dataclass
class Verdict:
    """One answer held against the reference."""

    missing: int = 0  # groups that pass HAVING but were not returned
    extra: int = 0  # groups returned that do not pass HAVING (or do not exist)
    max_rel_err: float = 0.0  # widest relative gap of a returned value

    @property
    def wrong_groups(self) -> int:
        return self.missing + self.extra


def compare(groupby: Sequence[str], group_values: Dict[str, np.ndarray],
            values: np.ndarray, exact: ExactGroups, index: Dict[tuple, int],
            op: str, threshold: float, rel_limit: float) -> Verdict:
    """Hold one answer (``group_values`` per returned group, ``values``)
    against the exact groups of its template under ``HAVING agg op
    threshold``."""
    values = np.asarray(values, dtype=np.float64)
    got = list(zip(*[np.asarray(group_values[a], dtype=np.float64).tolist()
                     for a in groupby])) if len(values) else []
    passes = _CMP[op](exact.values, threshold)
    near = np.abs(exact.values - threshold) <= rel_limit * max(abs(threshold), 1.0)
    v = Verdict()
    seen = set()
    for k, val in zip(got, values.tolist()):
        i = index.get(k)
        if i is None or (not passes[i] and not near[i]) or k in seen:
            v.extra += 1
            continue
        seen.add(k)
        want = exact.values[i]
        v.max_rel_err = max(v.max_rel_err, abs(val - want) / max(abs(want), 1e-30))
    required = np.nonzero(passes & ~near)[0]
    v.missing = sum(1 for i in required.tolist() if exact.keys[i] not in seen)
    return v


def answer_from_reference(exact: ExactGroups, groupby: Sequence[str], op: str,
                          threshold: float) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """The answer the reference itself gives (the control's stand-in for the
    engine's): groups whose aggregate passes HAVING, with their values."""
    keep = np.nonzero(_CMP[op](exact.values, threshold))[0]
    keys = np.array([exact.keys[i] for i in keep.tolist()], dtype=np.float64)
    keys = keys.reshape(len(keep), len(groupby))
    return ({a: keys[:, j] for j, a in enumerate(groupby)}, exact.values[keep])
