"""Query IR + executor for the paper's supported templates (Sec. 6.1).

Templates:
  Q-AGH    aggregation-groupby-having          (optional WHERE / HAVING)
  Q-AJGH   aggregation-join-groupby-having
  Q-AAGH   nested aggregation-aggregation-groupby-having
  Q-AAJGH  nested variant with a join in the inner block

The executor is a vectorized bag-semantics evaluator over ``ColumnTable``.
Group-by dictionary encodings, join layouts and bucketizations are *catalog*
state (``repro.core.catalog``) built once and reused across queries; per-row
aggregation runs on device through ``repro.kernels.ops.segment_aggregate``
(the one-hot MXU Pallas kernel on TPU, the ``jax.ops.segment_sum`` reference
path elsewhere).  The inner FROM/WHERE/GROUP BY/agg block is evaluated once
per query and its products are shared between result construction and
provenance derivation (``execute_and_provenance``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.catalog import Catalog, default_catalog
from repro.core.table import PAD_VALID, ColumnTable, Database
from repro.runtime.guards import hot_path
from repro.runtime.trace import span, to_host

Array = jax.Array

_OPS = {
    ">": lambda x, v: x > v,
    ">=": lambda x, v: x >= v,
    "<": lambda x, v: x < v,
    "<=": lambda x, v: x <= v,
    "=": lambda x, v: x == v,
}


@dataclasses.dataclass(frozen=True)
class Predicate:
    """Row-level WHERE predicate ``attr op value``."""

    attr: str
    op: str
    value: float

    def mask(self, table: ColumnTable) -> Array:
        return _OPS[self.op](table[self.attr], self.value)


@dataclasses.dataclass(frozen=True)
class Having:
    op: str
    value: float

    def mask(self, agg_values: Array) -> Array:
        return _OPS[self.op](agg_values, self.value)


@dataclasses.dataclass(frozen=True)
class Aggregate:
    fn: str  # 'sum' | 'avg' | 'count'
    attr: Optional[str] = None  # None for count(*)


@dataclasses.dataclass(frozen=True)
class JoinSpec:
    """Equi-join ``fact.left_key = right.right_key`` (right key unique)."""

    right: str
    left_key: str
    right_key: str


@dataclasses.dataclass(frozen=True)
class Query:
    table: str
    groupby: Tuple[str, ...]
    agg: Aggregate
    where: Optional[Predicate] = None
    having: Optional[Having] = None
    join: Optional[JoinSpec] = None
    # Nested templates (Q-AAGH / Q-AAJGH): outer block over the inner result.
    outer_groupby: Optional[Tuple[str, ...]] = None
    outer_agg: Optional[Aggregate] = None
    outer_having: Optional[Having] = None

    @property
    def template(self) -> str:
        nested = self.outer_groupby is not None
        joined = self.join is not None
        if nested and joined:
            return "Q-AAJGH"
        if nested:
            return "Q-AAGH"
        if joined:
            return "Q-AJGH"
        return "Q-AGH"

    @property
    def relevant_attrs(self) -> Tuple[str, ...]:
        """Attributes the query 'touches' (for RAND-REL-ALL / CB-OPT-REL)."""
        attrs = list(self.groupby)
        if self.agg.attr:
            attrs.append(self.agg.attr)
        if self.where is not None:
            attrs.append(self.where.attr)
        if self.join is not None:
            attrs.append(self.join.left_key)
        if self.outer_groupby:
            attrs.extend(self.outer_groupby)
        seen, out = set(), []
        for a in attrs:
            if a not in seen:
                seen.add(a)
                out.append(a)
        return tuple(out)

    def groupby_on_fact(self, db: "Database") -> Tuple[str, ...]:
        """Group-by attributes that live on the sketched (fact) relation."""
        fact = db[self.table]
        return tuple(a for a in self.groupby if fact.has(a))

    def inner_signature(self) -> Tuple:
        """Hashable identity of the inner block (FROM/WHERE/GROUP BY/agg) —
        everything the HAVING chain does *not* affect.  Queries with equal
        inner signatures share samples, AQR estimate passes, inner-block
        evaluations and maintainer counting state; the batched admission
        pipeline and the AQR cache both key on this one helper so the
        sharing assumptions cannot drift apart."""
        return (
            self.table,
            self.groupby,
            (self.agg.fn, self.agg.attr),
            dataclasses.astuple(self.where) if self.where else None,
            dataclasses.astuple(self.join) if self.join else None,
        )

    def signature(self) -> Tuple:
        """Hashable identity used by the sketch index."""
        return (
            self.table,
            self.groupby,
            (self.agg.fn, self.agg.attr),
            dataclasses.astuple(self.where) if self.where else None,
            dataclasses.astuple(self.having) if self.having else None,
            dataclasses.astuple(self.join) if self.join else None,
            self.outer_groupby,
            (self.outer_agg.fn, self.outer_agg.attr) if self.outer_agg else None,
            dataclasses.astuple(self.outer_having) if self.outer_having else None,
        )


@dataclasses.dataclass(frozen=True)
class QueryResult:
    group_values: Dict[str, np.ndarray]  # per surviving group
    values: np.ndarray  # aggregate per surviving group

    def canonical(self) -> Tuple[Tuple, ...]:
        """Order-independent representation for result-equality tests."""
        attrs = sorted(self.group_values)
        rows = []
        for i in range(len(self.values)):
            rows.append(
                tuple(float(self.group_values[a][i]) for a in attrs)
                + (round(float(self.values[i]), 6),)
            )
        return tuple(sorted(rows))


# ---------------------------------------------------------------------------
# Aggregation primitives
# ---------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def segment_sums_counts(
    values: Array, gid: Array, n_groups: int, weights: Optional[Array] = None
) -> Tuple[Array, Array]:
    """(per-group sums, per-group counts) via the segment-aggregate kernel.

    Dispatches the Pallas one-hot MXU kernel on TPU and the segment-sum
    reference path elsewhere (see ``repro.kernels.ops``).  Row and group
    dimensions are padded to powers of two so the jitted kernel wrapper
    compiles once per size class instead of once per query shape.  The
    mask goes to the kernel in its own dtype: its cast to float32 then runs
    inside the kernel's jit, not as a dispatch of its own before it.
    """
    from repro.kernels import ops as kops

    n = int(values.shape[0])
    n_pad = _next_pow2(max(n, 1))
    g_pad = _next_pow2(max(n_groups, 1))
    w = jnp.ones(n, dtype=bool) if weights is None else weights
    if n_pad != n:
        # Padded rows carry weight 0 into group 0: they contribute nothing.
        values = jnp.pad(values.astype(jnp.float32), (0, n_pad - n))
        gid = jnp.pad(gid.astype(jnp.int32), (0, n_pad - n))
        w = jnp.pad(w, (0, n_pad - n))
    sums, counts = kops.segment_aggregate(values, gid, g_pad, w)
    return sums[:n_groups], counts[:n_groups]


def _finalize_aggregate(fn: str, sums: Array, counts: Array) -> Array:
    if fn == "count":
        return counts
    if fn == "sum":
        return sums
    if fn == "avg":
        return sums / jnp.maximum(counts, 1.0)
    raise ValueError(f"unknown aggregate {fn!r}")


@hot_path
def segment_aggregate(
    values: Array, gid: Array, n_groups: int, fn: str, weights: Optional[Array] = None
) -> Array:
    """Per-group aggregate; ``weights`` is the row inclusion mask (WHERE)."""
    sums, counts = segment_sums_counts(values, gid, n_groups, weights)
    return _finalize_aggregate(fn, sums, counts)


# ---------------------------------------------------------------------------
# Join materialization (right key unique, e.g. orders.orderkey, part.partkey)
# ---------------------------------------------------------------------------


def materialize_join(
    db: Database, q: Query, catalog: Optional[Catalog] = None
) -> Tuple[ColumnTable, np.ndarray]:
    """Return the joined flat table and, per joined row, the fact-row index.

    The layout is built once per (fact, right, keys) in the catalog and
    reused by every subsequent query over the same join spec.
    """
    catalog = catalog or default_catalog()
    return catalog.join(db[q.table], db[q.join.right], q.join.left_key, q.join.right_key)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class InnerBlock:
    """Products of the FROM/WHERE/GROUP BY/agg inner block, computed once.

    ``fact_idx`` maps flat rows back to fact-table rows (``None`` means the
    identity — no join).  ``present[g]`` is True iff group ``g`` has at least
    one row passing WHERE.
    """

    flat: ColumnTable
    fact_idx: Optional[np.ndarray]
    gid: np.ndarray
    n_groups: int
    group_values: Dict[str, np.ndarray]
    agg_np: np.ndarray
    present: np.ndarray
    where_np: np.ndarray


def inner_block_arrays(q: Query, flat: ColumnTable, catalog: Catalog):
    """The per-row inputs of the inner block's aggregation over one (already
    joined) flat table: ``(enc, where_mask, vals)``.

    The single source of truth for mask derivation (WHERE ∧ pad-validity),
    group encoding and aggregate value selection.  Single-node execution
    feeds the arrays straight into ``segment_sums_counts``; the fragment-
    sharded stacked launch (``repro.core.shard``) pads and stacks them on a
    shard axis first — either way the aggregation semantics come from here,
    which is what makes routed partials mergeable into bit-identical results.
    """
    with span("repro.where"):
        where_mask = (
            catalog.where_mask(flat, q.where)
            if q.where is not None
            else jnp.ones(flat.num_rows, dtype=bool)
        )
        if flat.has(PAD_VALID):
            # Pow2-padded sketch instance: the tail rows exist only to pin the
            # compiled shape and must contribute nothing (weight 0 everywhere).
            where_mask = where_mask & flat[PAD_VALID]
    with span("repro.groups"):
        enc = catalog.groups(flat, q.groupby)
    if q.agg.fn == "count":
        vals = jnp.ones(flat.num_rows, dtype=jnp.float32)
    else:
        vals = flat[q.agg.attr]
    return enc, where_mask, vals


def inner_group_partials(
    q: Query, flat: ColumnTable, catalog: Catalog
):
    """WHERE mask + group encoding + fused per-group sums/counts over one
    (already joined) flat table.  Returns ``(enc, where_mask, sums, counts)``.
    """
    enc, where_mask, vals = inner_block_arrays(q, flat, catalog)
    with span("repro.aggregate"):
        sums, counts = segment_sums_counts(vals, enc.gid_dev, enc.n_groups, weights=where_mask)
    return enc, where_mask, sums, counts


def _inner_block(db: Database, q: Query, catalog: Optional[Catalog] = None) -> InnerBlock:
    """Evaluate the inner block once; one fused segment pass yields both the
    aggregate values and group presence.  The first copy to the host waits
    for the aggregation kernel."""
    catalog = catalog or default_catalog()
    if q.join is not None:
        flat, fact_idx = materialize_join(db, q, catalog)
    else:
        flat, fact_idx = db[q.table], None
    enc, where_mask, sums, counts = inner_group_partials(q, flat, catalog)
    agg = _finalize_aggregate(q.agg.fn, sums, counts)
    counts_np = to_host(counts)  # analyze: waive[SYNC01]: the inner block's result reaches the host here, once per query
    return InnerBlock(
        flat=flat,
        fact_idx=fact_idx,
        gid=enc.gid,
        n_groups=enc.n_groups,
        group_values=enc.group_values,
        agg_np=to_host(agg),  # analyze: waive[SYNC01]: per-group aggregates for the host's HAVING, once per query
        # Groups whose every row fails the WHERE do not appear in the result.
        present=counts_np > 0,
        where_np=to_host(where_mask),  # analyze: waive[SYNC01]: row mask for provenance derivation, once per query
    )


def result_from_group_state(
    q: Query,
    group_values: Dict[str, np.ndarray],
    agg_np: np.ndarray,
    present: np.ndarray,
) -> QueryResult:
    """Finish a query from per-group state alone (HAVING chain + outer block).

    This is the group-level tail of the executor, factored out so the
    fragment-sharded coordinator (``repro.core.shard``) can run it over
    *merged* per-shard partial aggregates: given equal per-group values and
    presence, the result matches single-node execution exactly.
    """
    with span("repro.having"):
        return _finish_group_state(q, group_values, agg_np, present)


def _finish_group_state(
    q: Query,
    group_values: Dict[str, np.ndarray],
    agg_np: np.ndarray,
    present: np.ndarray,
) -> QueryResult:
    if q.outer_groupby is None:
        keep = present.copy()
        if q.having is not None:
            keep &= np.asarray(q.having.mask(agg_np))
        idx = np.nonzero(keep)[0]
        return QueryResult(
            group_values={a: v[idx] for a, v in group_values.items()},
            values=agg_np[idx],
        )

    # Nested templates: inner HAVING filters inner groups, then the outer
    # block aggregates result1 over outer_groupby (subset of inner groupby).
    inner_keep = present.copy()
    if q.having is not None:
        inner_keep &= np.asarray(q.having.mask(agg_np))
    inner_idx = np.nonzero(inner_keep)[0]
    inner_vals = agg_np[inner_idx]
    inner_gv = {a: v[inner_idx] for a, v in group_values.items()}

    stacked = np.stack([inner_gv[a] for a in q.outer_groupby], axis=1)
    if stacked.shape[0] == 0:
        return QueryResult(group_values={a: np.empty(0) for a in q.outer_groupby}, values=np.empty(0))
    uniq, ogid = np.unique(stacked, axis=0, return_inverse=True)
    n_outer = uniq.shape[0]
    outer_vals = segment_aggregate(
        jnp.asarray(inner_vals),
        jnp.asarray(ogid.astype(np.int32)),
        n_outer,
        q.outer_agg.fn if q.outer_agg else "sum",
    )
    outer_np = to_host(outer_vals)  # analyze: waive[SYNC01]: deliberate merge: outer-query HAVING filters on host, once per query result
    keep = np.ones(n_outer, dtype=bool)
    if q.outer_having is not None:
        keep &= np.asarray(q.outer_having.mask(outer_np))
    idx = np.nonzero(keep)[0]
    return QueryResult(
        group_values={a: uniq[:, i][idx] for i, a in enumerate(q.outer_groupby)},
        values=outer_np[idx],
    )


def _result_from_inner(q: Query, ib: InnerBlock) -> QueryResult:
    return result_from_group_state(q, ib.group_values, ib.agg_np, ib.present)


def provenance_group_keep(
    q: Query,
    agg_np: np.ndarray,
    group_values: Dict[str, np.ndarray],
    n_groups: int,
) -> np.ndarray:
    """Which (inner) groups survive the HAVING chain, per-group-state only.

    This is the group-level half of provenance derivation, factored out so the
    incremental maintenance path (``repro.core.maintenance``) can replay it
    bit-for-bit from *maintained* per-group aggregates: given equal ``agg_np``
    and group key values, the surviving-group set — and hence the sketch bits
    — matches a from-scratch capture exactly.  Group *numbering* may differ
    between callers; the outer block re-keys on group values, so the result
    is numbering-covariant.
    """
    inner_keep = np.ones(n_groups, dtype=bool)
    if q.having is not None:
        inner_keep &= np.asarray(q.having.mask(agg_np))

    if q.outer_groupby is not None:
        inner_idx = np.nonzero(inner_keep)[0]
        if inner_idx.shape[0]:
            stacked = np.stack(
                [group_values[a][inner_idx] for a in q.outer_groupby], axis=1
            )
            uniq, ogid = np.unique(stacked, axis=0, return_inverse=True)
            outer_vals = to_host(  # analyze: waive[SYNC01]: deliberate merge: nested-aggregate outer pass filters on host, once per query result
                segment_aggregate(
                    jnp.asarray(agg_np[inner_idx]),
                    jnp.asarray(ogid.astype(np.int32)),
                    uniq.shape[0],
                    q.outer_agg.fn if q.outer_agg else "sum",
                )
            )
            outer_keep = np.ones(uniq.shape[0], dtype=bool)
            if q.outer_having is not None:
                outer_keep &= np.asarray(q.outer_having.mask(outer_vals))
            surviving_inner = np.zeros(n_groups, dtype=bool)
            surviving_inner[inner_idx] = outer_keep[ogid]
            inner_keep = surviving_inner
        else:
            inner_keep = np.zeros(n_groups, dtype=bool)
    return inner_keep


def _provenance_from_inner(q: Query, ib: InnerBlock, n_fact_rows: int) -> np.ndarray:
    inner_keep = provenance_group_keep(q, ib.agg_np, ib.group_values, ib.n_groups)
    row_keep = inner_keep[ib.gid] & ib.where_np
    if ib.fact_idx is None:
        return row_keep
    mask = np.zeros(n_fact_rows, dtype=bool)
    mask[ib.fact_idx[row_keep]] = True
    return mask


# Public names for the inner-block products: the batched admission pipeline
# (``repro.core.admission``) evaluates the shared FROM/WHERE/GROUP BY/agg
# block once per signature group and derives every member query's result and
# provenance from the same ``InnerBlock`` — the group-level tails are pure
# functions of it, so sharing is bit-exact.
inner_block = _inner_block
result_from_inner = _result_from_inner
provenance_from_inner = _provenance_from_inner


@hot_path
def execute(q: Query, db: Database, catalog: Optional[Catalog] = None) -> QueryResult:
    return _result_from_inner(q, _inner_block(db, q, catalog))


def provenance_mask(q: Query, db: Database, catalog: Optional[Catalog] = None) -> np.ndarray:
    """Lineage P(Q, D) as a boolean mask over the *fact table* rows.

    A fact row is in the provenance iff it contributes to some result tuple:
    it satisfies WHERE, joins (for join templates), and its group survives the
    HAVING chain.  This is the sufficiency-preserving lineage of Sec. 2.2.
    """
    ib = _inner_block(db, q, catalog)
    return _provenance_from_inner(q, ib, db[q.table].num_rows)


@hot_path
def execute_and_provenance(
    q: Query, db: Database, catalog: Optional[Catalog] = None
) -> Tuple[QueryResult, np.ndarray]:
    """Fused capture+execute path: one inner-block evaluation yields both the
    query result and the provenance mask (the seed ran the block twice)."""
    ib = _inner_block(db, q, catalog)
    return _result_from_inner(q, ib), _provenance_from_inner(q, ib, db[q.table].num_rows)
