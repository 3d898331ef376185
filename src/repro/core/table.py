"""Columnar tables: the storage substrate for provenance-based data skipping.

The paper's engine runs on Postgres heap tables; the TPU-native equivalent is a
struct-of-arrays ``ColumnTable`` whose columns are device-resident 1-D arrays.
Fragments of a range partition are *logical* row subsets; the fragment-major
physical layout (``sort_by``) makes a fragment a contiguous tile so that data
skipping maps to "do not issue the HBM->VMEM copy for this tile".
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Monotone lineage ids: every freshly created relation gets a new uid; append/
# delete/cluster_by preserve it while bumping (or keeping) the version token,
# so caches and sketch maintainers can tell "same relation, newer contents"
# apart from "a different relation entirely".
_TABLE_UIDS = itertools.count(1)

# Reserved column marking padded tables (sketch instances): True for
# real rows, False for the shape-pinning tail.  The executor folds it into
# the aggregation weights so padded and unpadded execution agree bit-for-bit.
PAD_VALID = "__valid__"


def _bucketize_np(bounds: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Host-side fragment ids with ``RangeSet.bucketize``'s exact comparison
    semantics: ``jnp.searchsorted`` compares in float32 when x64 is disabled,
    so a float64 ``np.searchsorted`` could place boundary-adjacent values in
    a different fragment than every cached bucketization and sketch bit in
    the system.  All host-side tail bucketing must go through here."""
    return np.searchsorted(bounds.astype(np.float32),
                           np.asarray(values).astype(np.float32), side="right")


@dataclasses.dataclass(frozen=True, eq=False)
class FragmentLayout:
    """Physical fragment-major layout descriptor for a clustered table.

    After ``cluster_by(ranges)`` every fragment of the range partition is a
    contiguous row slice ``[offsets[f], offsets[f+1])``, so applying a sketch
    on the same partition degenerates to concatenating the surviving slices —
    no per-row filter scan.  Identity-hashed (``eq=False``) so it can ride in
    pytree aux data.

    ``tail`` is the number of trailing rows *not* covered by ``offsets``:
    appends land in an unsorted tail region so a batch insert does not force
    a physical re-cluster.  Sketch application then concatenates the prefix
    slices and filters only the tail rows (delta-sized work).
    """

    attr: str
    ranges_key: Tuple
    offsets: np.ndarray  # (n_fragments + 1,) row offsets, offsets[0] == 0
    tail: int = 0

    @property
    def n_fragments(self) -> int:
        return int(self.offsets.shape[0]) - 1

    def matches(self, ranges) -> bool:
        return self.attr == ranges.attr and self.ranges_key == ranges.key()

    def bounds(self) -> np.ndarray:
        """The partition's interior split points, recovered from the key.

        ``ranges_key`` is ``RangeSet.key() == (attr, n_ranges, bounds bytes)``;
        round-tripping the bytes lets layout-only consumers (tail bucketing in
        ``take_fragments``, ``compact``) re-bucketize appended rows without
        threading the original ``RangeSet`` through every call site.
        """
        bounds = np.frombuffer(self.ranges_key[2], dtype=np.float64)
        if bounds.shape[0] != self.ranges_key[1] - 1:
            raise ValueError("layout ranges_key does not hold float64 bounds")
        return bounds


@dataclasses.dataclass(frozen=True, eq=False)
class TableDelta:
    """One append/delete step linking a table version to its parent.

    The delta is what makes incremental maintenance possible: catalog caches
    refresh themselves from the parent entry plus the delta (no full-table
    re-encode / re-bucketize), and ``repro.core.maintenance`` re-ORs sketch
    bits only for touched fragments.  ``parent`` is a strong reference so
    id()-keyed parent cache entries stay valid while the delta is reachable.
    """

    kind: str  # 'append' | 'delete'
    parent: "ColumnTable"
    appended: Optional["ColumnTable"] = None  # kind='append': the new rows
    deleted_idx: Optional[np.ndarray] = None  # kind='delete': parent rows removed
    kept_idx: Optional[np.ndarray] = None  # kind='delete': parent rows kept

    @property
    def n_delta(self) -> int:
        if self.kind == "append":
            return self.appended.num_rows
        return int(self.deleted_idx.shape[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ColumnTable:
    """An immutable bag-semantics relation stored column-major.

    Attributes:
      name: relation name (static / aux data, not traced).
      columns: mapping attribute -> 1-D array; all columns share length.
      primary_key: attribute names forming the primary key (may be empty).
      layout: fragment-major physical layout, set by ``cluster_by`` (row-
        reordering operations drop it; appends push rows into its tail).
      version: monotone per-lineage version token, bumped by append/delete.
      uid: lineage identity — preserved by append/delete/cluster_by, fresh
        for any other derived table (gather/select/head/...).
      delta: the append/delete step that produced this version (None for a
        root table); the hook for incremental catalog refresh + maintenance.
    """

    name: str
    columns: Dict[str, Array]
    primary_key: Tuple[str, ...] = ()
    layout: Optional[FragmentLayout] = None
    version: int = 0
    uid: int = 0
    delta: Optional[TableDelta] = None

    def __post_init__(self):
        if self.uid == 0:
            object.__setattr__(self, "uid", next(_TABLE_UIDS))

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        keys = tuple(sorted(self.columns))
        children = tuple(self.columns[k] for k in keys)
        aux = (self.name, keys, self.primary_key, self.layout, self.version,
               self.uid, self.delta)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        name, keys, pk, layout, version, uid, delta = aux
        return cls(name=name, columns=dict(zip(keys, children)), primary_key=pk,
                   layout=layout, version=version, uid=uid, delta=delta)

    # -- basic accessors -----------------------------------------------------
    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 0
        return int(next(iter(self.columns.values())).shape[0])

    @property
    def schema(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    def __getitem__(self, attr: str) -> Array:
        return self.columns[attr]

    def has(self, attr: str) -> bool:
        return attr in self.columns

    # -- functional updates ----------------------------------------------------
    def with_column(self, attr: str, values: Array) -> "ColumnTable":
        cols = dict(self.columns)
        cols[attr] = values
        # Row order is unchanged, so the physical layout survives.
        return ColumnTable(self.name, cols, self.primary_key, self.layout)

    def select(self, mask: Array) -> "ColumnTable":
        """Keep rows where ``mask`` is True (host-side compaction)."""
        idx = jnp.nonzero(np.asarray(mask))[0]
        return self.gather(idx)

    def gather(self, idx: Array) -> "ColumnTable":
        return ColumnTable(
            self.name,
            {k: jnp.take(v, idx, axis=0) for k, v in self.columns.items()},
            self.primary_key,
        )

    def sort_by(self, attrs: Sequence[str]) -> "ColumnTable":
        """Physically order rows by ``attrs``."""
        keys = [np.asarray(self.columns[a]) for a in reversed(list(attrs))]
        order = np.lexsort(keys)
        return self.gather(jnp.asarray(order))

    def cluster_by(self, ranges) -> "ColumnTable":
        """Fragment-major physical layout for a range partition.

        Rows are stably reordered by fragment id so fragment ``f`` occupies
        the contiguous slice ``[offsets[f], offsets[f+1])``; the resulting
        ``FragmentLayout`` makes sketch application a concatenation of the
        surviving slices (see ``repro.core.sketch.apply_sketch``).
        """
        bucket = np.asarray(ranges.bucketize(self[ranges.attr]))
        order = np.argsort(bucket, kind="stable")
        counts = np.bincount(bucket, minlength=ranges.n_ranges)
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        clustered = self.gather(jnp.asarray(order))
        layout = FragmentLayout(attr=ranges.attr, ranges_key=ranges.key(), offsets=offsets)
        # Same relation contents, physically permuted: lineage and version
        # survive (sketch/maintainer state is permutation-invariant) but the
        # delta chain does not — parent row positions no longer line up.
        return ColumnTable(self.name, clustered.columns, self.primary_key, layout,
                           version=self.version, uid=self.uid)

    def take_fragments(
        self, frag_ids: np.ndarray, tail_bucket: Optional[np.ndarray] = None,
        return_rows: bool = False,
    ):
        """Concatenate the given fragments' contiguous slices (clustered only).

        Appended rows live in the layout's unsorted ``tail``; they are
        bucket-filtered individually (delta-sized work) against ``frag_ids``
        rather than invalidating the slice path.  ``tail_bucket`` — the tail
        rows' fragment ids — may be passed in when the caller holds a cached
        (delta-refreshed) bucketization; otherwise it is recomputed here from
        the layout's own bounds.  With ``return_rows`` the selected source
        row indices are returned alongside (the catalog's instance-encoding
        derivation needs the subset map).
        """
        if self.layout is None:
            raise ValueError(f"{self.name}: take_fragments needs a clustered table")
        lay = self.layout
        frag_ids = np.asarray(frag_ids)
        off = lay.offsets
        parts = [np.arange(off[f], off[f + 1]) for f in frag_ids]
        if lay.tail:
            n = self.num_rows
            if tail_bucket is None:
                tail_vals = np.asarray(self[lay.attr])[n - lay.tail:]
                tail_bucket = _bucketize_np(lay.bounds(), tail_vals)
            tail_bucket = np.asarray(tail_bucket)
            if tail_bucket.shape[0] != lay.tail:
                raise ValueError(
                    f"tail_bucket has {tail_bucket.shape[0]} entries for a "
                    f"{lay.tail}-row tail")
            keep = np.zeros(lay.n_fragments, dtype=bool)
            keep[frag_ids] = True
            parts.append(np.arange(n - lay.tail, n)[keep[tail_bucket]])
        idx = np.concatenate(parts) if parts else np.empty(0, dtype=np.int64)
        out = self.gather(jnp.asarray(idx))
        return (out, idx) if return_rows else out

    def compact(self) -> "ColumnTable":
        """Fold the layout's unsorted tail back into fragment-major order.

        Same contents, lineage and version (a physical permutation, like
        ``cluster_by``), with ``tail == 0`` afterwards so sketch application
        is pure slice concatenation again.  Row-position caches (samples,
        bucketizations, instances) must be invalidated by the caller — the
        delta chain is dropped for the same reason as in ``cluster_by``.
        """
        lay = self.layout
        if lay is None or lay.tail == 0:
            return self.collapse()
        n = self.num_rows
        tail_rows = np.arange(n - lay.tail, n)
        tail_vals = np.asarray(self[lay.attr])[tail_rows]
        tail_bucket = _bucketize_np(lay.bounds(), tail_vals)
        order_t = np.argsort(tail_bucket, kind="stable")
        # Merge each tail run into its fragment, after the existing rows
        # (stable: prefix rows keep their relative order, tail rows append).
        tail_counts = np.bincount(tail_bucket, minlength=lay.n_fragments)
        new_offsets = np.concatenate(
            [[0], np.cumsum(np.diff(lay.offsets) + tail_counts)]).astype(np.int64)
        parts = []
        t_off = np.concatenate([[0], np.cumsum(tail_counts)])
        for f in range(lay.n_fragments):
            parts.append(np.arange(lay.offsets[f], lay.offsets[f + 1]))
            parts.append(tail_rows[order_t[t_off[f]:t_off[f + 1]]])
        idx = np.concatenate(parts)
        compacted = self.gather(jnp.asarray(idx))
        layout = FragmentLayout(attr=lay.attr, ranges_key=lay.ranges_key,
                                offsets=new_offsets)
        return ColumnTable(self.name, compacted.columns, self.primary_key, layout,
                           version=self.version, uid=self.uid)

    # -- mutations (delta-aware) ----------------------------------------------
    def delta_depth(self) -> int:
        """Length of the delta chain behind this version."""
        depth, t = 0, self
        while t.delta is not None:
            depth += 1
            t = t.delta.parent
        return depth

    def collapse(self) -> "ColumnTable":
        """Drop the delta history: same contents, version and lineage, no
        parent references.  Bounds memory — every prior version's columns are
        pinned by the chain — at the cost of one full-cache rebuild for
        consumers that would have delta-refreshed (see
        ``PBDSEngine.max_delta_chain``)."""
        if self.delta is None:
            return self
        return ColumnTable(self.name, self.columns, self.primary_key, self.layout,
                           version=self.version, uid=self.uid)

    def append(self, rows: Mapping[str, np.ndarray]) -> "ColumnTable":
        """Append a batch of rows, producing the next table version.

        The new version carries a ``TableDelta`` so catalog entries and
        provenance sketches refresh from the batch alone.  A fragment-major
        layout survives: the batch lands in the layout's unsorted ``tail``
        region rather than forcing a physical re-cluster.
        """
        if set(rows) != set(self.columns):
            raise ValueError(
                f"append schema mismatch: {sorted(rows)} vs {sorted(self.columns)}")
        batch = {}
        for k, v in rows.items():
            src = np.asarray(v)
            dst = src.astype(self.columns[k].dtype)
            # Reject lossy coercion at the mutation boundary: silently
            # truncated/wrapped values would flow through every maintained
            # aggregate undetectably.
            if not np.array_equal(dst.astype(np.float64), src.astype(np.float64),
                                  equal_nan=True):
                raise ValueError(
                    f"append column {k!r}: lossy cast {src.dtype} -> "
                    f"{self.columns[k].dtype}")
            batch[k] = jnp.asarray(dst)
        lengths = {int(v.shape[0]) for v in batch.values()}
        if len(lengths) != 1:
            raise ValueError(f"ragged append batch: { {k: int(v.shape[0]) for k, v in batch.items()} }")
        n_new = lengths.pop()
        appended = ColumnTable(self.name, batch, self.primary_key)
        cols = {k: jnp.concatenate([self.columns[k], batch[k]]) for k in self.columns}
        layout = (dataclasses.replace(self.layout, tail=self.layout.tail + n_new)
                  if self.layout is not None else None)
        return ColumnTable(
            self.name, cols, self.primary_key, layout,
            version=self.version + 1, uid=self.uid,
            delta=TableDelta(kind="append", parent=self, appended=appended),
        )

    def delete(self, mask: np.ndarray) -> "ColumnTable":
        """Delete the rows where ``mask`` is True, producing the next version.

        Compaction preserves relative row order, so a fragment-major layout
        survives with shrunk offsets (per-fragment deletion counts follow from
        the offsets themselves — no re-bucketization).
        """
        mask = np.asarray(mask, dtype=bool)
        if mask.shape[0] != self.num_rows:
            raise ValueError(f"delete mask length {mask.shape[0]} != {self.num_rows} rows")
        deleted_idx = np.nonzero(mask)[0]
        kept_idx = np.nonzero(~mask)[0]
        cols = {k: jnp.take(v, jnp.asarray(kept_idx), axis=0) for k, v in self.columns.items()}
        layout = None
        if self.layout is not None:
            lay = self.layout
            prefix_len = self.num_rows - lay.tail
            del_prefix = deleted_idx[deleted_idx < prefix_len]
            frag_of_deleted = np.searchsorted(lay.offsets, del_prefix, side="right") - 1
            counts = np.diff(lay.offsets) - np.bincount(
                frag_of_deleted, minlength=lay.n_fragments)
            offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
            tail = lay.tail - int((deleted_idx >= prefix_len).sum())
            layout = FragmentLayout(attr=lay.attr, ranges_key=lay.ranges_key,
                                    offsets=offsets, tail=tail)
        return ColumnTable(
            self.name, cols, self.primary_key, layout,
            version=self.version + 1, uid=self.uid,
            delta=TableDelta(kind="delete", parent=self,
                             deleted_idx=deleted_idx, kept_idx=kept_idx),
        )

    def head(self, n: int) -> "ColumnTable":
        return ColumnTable(
            self.name,
            {k: v[:n] for k, v in self.columns.items()},
            self.primary_key,
        )

    def to_numpy(self) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in self.columns.items()}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"ColumnTable({self.name!r}, rows={self.num_rows}, cols={list(self.schema)})"


def from_numpy(
    name: str,
    data: Mapping[str, np.ndarray],
    primary_key: Iterable[str] = (),
) -> ColumnTable:
    cols = {k: jnp.asarray(v) for k, v in data.items()}
    lengths = {k: int(v.shape[0]) for k, v in cols.items()}
    if len(set(lengths.values())) > 1:
        raise ValueError(f"ragged columns: {lengths}")
    return ColumnTable(name, cols, tuple(primary_key))


@dataclasses.dataclass(frozen=True)
class Database:
    """A named collection of tables (the ``D`` of the paper)."""

    tables: Dict[str, ColumnTable]

    def __getitem__(self, name: str) -> ColumnTable:
        return self.tables[name]

    def with_table(self, table: ColumnTable) -> "Database":
        t = dict(self.tables)
        t[table.name] = table
        return Database(t)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.tables))


def encode_groups(
    table: ColumnTable, attrs: Sequence[str]
) -> Tuple[np.ndarray, int, Dict[str, np.ndarray]]:
    """Dictionary-encode the group-by key.

    Returns ``(gid, n_groups, group_values)`` where ``gid[i]`` is the dense
    group id of row ``i`` and ``group_values[a][g]`` is the value of attribute
    ``a`` for group ``g``.  Host-side (``np.unique``), mirroring the catalog /
    dictionary structures a DBMS maintains; the per-row heavy lifting stays on
    device.
    """
    if not attrs:
        n = table.num_rows
        return np.zeros(n, dtype=np.int32), 1, {}
    stacked = np.stack([np.asarray(table[a]) for a in attrs], axis=1)
    uniq, gid = np.unique(stacked, axis=0, return_inverse=True)
    group_values = {a: uniq[:, i] for i, a in enumerate(attrs)}
    return gid.astype(np.int32), int(uniq.shape[0]), group_values
