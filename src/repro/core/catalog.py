"""Device-resident query catalog: the per-query host work, done once.

The paper's premise is that sketch *application* is nearly free — skipping is
a scheduling decision, not a scan.  The seed executor violated that premise on
every query: group-by keys were re-dictionary-encoded on host (``np.unique``),
joins re-materialized (``np.argsort`` + searchsorted), partition attributes
re-bucketized, and each sketch application gathered a filtered copy of the
whole relation.  The ``Catalog`` is the DBMS-style fix: per table it caches

  * the dictionary encoding of every seen GROUP BY tuple (dense gids, host
    and device copies, plus per-group key values),
  * the bucketization vector of every candidate partition attribute under a
    given ``RangeSet`` (a device array reused by capture, application, and
    size estimation),
  * the materialized join layout per join spec (joined columns + the
    fact-row back-map); a sketch instance's join is a gather of its base
    table's layout,
  * per-sketch *instances* (the filtered relation D_P), so an index hit
    re-executes over an already-materialized fragment subset,
  * cheap per-attribute statistics (distinct counts, non-negativity) used by
    the safety pre-filter.

Tables are immutable *values*, but a relation evolves through versions:
``ColumnTable.append`` / ``.delete`` produce a new object carrying a
``TableDelta`` back-pointer.  Entries are keyed by object identity with a
strong reference held for validity; a cache miss on a table that has a delta
is *refreshed incrementally* from the parent's entry (bucketize the batch and
concatenate, extend the group dictionary with the batch's keys, add/subtract
per-fragment counts) instead of redoing the full-table host work.  The
``*_delta`` stat counters separate that delta-sized work from full misses, so
tests can assert the delta path never re-bucketizes a whole table.  ``stats``
counts cache misses (real work) and hits, which the tests use to assert that
a repeated workload does zero host-side encode/argsort work.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import TYPE_CHECKING, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import ColumnTable, encode_groups
from repro.runtime.trace import count, span

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.ranges import RangeSet

Array = jax.Array


@dataclasses.dataclass
class GroupEncoding:
    """Cached dictionary encoding of one GROUP BY tuple on one table."""

    gid: np.ndarray  # dense group id per row (host)
    gid_dev: Array  # same, device-resident
    n_groups: int
    group_values: Dict[str, np.ndarray]  # per-group key values
    _key_index: Optional[Dict[Tuple, int]] = None  # lazy key-tuple -> gid

    def key_index(self, attrs: Tuple[str, ...]) -> Dict[Tuple, int]:
        """key tuple -> gid, built lazily (delta refresh needs the lookup)."""
        if self._key_index is None:
            cols = [self.group_values[a].tolist() for a in attrs]
            self._key_index = {key: g for g, key in enumerate(zip(*cols))} if cols else {(): 0}
        return self._key_index


def map_group_keys(
    stacked: np.ndarray, key_index: Dict[Tuple, int], n_groups: int,
    grow: bool = True,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Map a batch of stacked group-key rows through an existing dictionary.

    Known keys take their existing gid; unseen ones get fresh ids appended
    (``key_index`` is mutated in place) — or raise ``KeyError`` when
    ``grow=False``.  The shared primitive behind catalog encoding refresh,
    sketch maintainers and sample extension, so the stable-gid-numbering
    invariant lives in exactly one place.  Returns ``(gid per batch row,
    unseen unique key rows in assignment order, new group count)``; per-row
    work is vectorized, the Python loop touches only *unique* batch keys.
    """
    uniq, inv = np.unique(stacked, axis=0, return_inverse=True)
    mapped = np.empty(uniq.shape[0], dtype=np.int64)
    new_rows = []
    for i, row in enumerate(uniq):
        key = tuple(row.tolist())
        g = key_index.get(key)
        if g is None:
            if not grow:
                raise KeyError(key)
            g = n_groups
            key_index[key] = g
            n_groups += 1
            new_rows.append(i)
        mapped[i] = g
    return mapped[inv], uniq[new_rows], n_groups


def extend_group_values(
    group_values: Dict[str, np.ndarray],
    attrs: Tuple[str, ...],
    new_keys: np.ndarray,
) -> Dict[str, np.ndarray]:
    """Append freshly assigned groups' key values (dtype-preserving).

    Companion to ``map_group_keys``: ``new_keys`` is its unseen-unique-rows
    output, column ``j`` holding attribute ``attrs[j]``.  Returns a new dict
    (inputs are shared with live cache entries and must not mutate).
    """
    if not len(new_keys):
        return group_values
    return {
        a: np.concatenate([group_values[a],
                           new_keys[:, j].astype(group_values[a].dtype, copy=False)])
        for j, a in enumerate(attrs)
    }


def extend_encoding(
    parent: GroupEncoding, batch: ColumnTable, attrs: Tuple[str, ...]
) -> GroupEncoding:
    """Dictionary-encode ``batch`` against ``parent``'s group dictionary.

    Known keys map to their existing gid; unseen keys get fresh ids appended,
    so downstream per-group state (aggregates, incidence counters) stays
    aligned and only grows.  Work is O(batch + new groups), never O(table).
    """
    if not attrs:
        gid = np.concatenate([parent.gid, np.zeros(batch.num_rows, dtype=np.int32)])
        return GroupEncoding(gid, jnp.asarray(gid), parent.n_groups, parent.group_values)
    stacked = np.stack([np.asarray(batch[a]) for a in attrs], axis=1)
    key_index = dict(parent.key_index(attrs))  # copy: parent entry stays valid
    delta_gid, new_keys, n_groups = map_group_keys(stacked, key_index, parent.n_groups)
    group_values = extend_group_values(parent.group_values, attrs, new_keys)
    gid = np.concatenate([parent.gid, delta_gid]).astype(np.int32)
    return GroupEncoding(gid, jnp.asarray(gid), n_groups, group_values, key_index)


def join_rows(
    fact_cols: Dict[str, np.ndarray],
    right: ColumnTable,
    left_key: str,
    right_key: str,
) -> Tuple[Dict[str, Array], np.ndarray, np.ndarray]:
    """Inner equi-join of a column batch against ``right`` (right key unique).

    Returns ``(joined columns, matched batch row ids, right row ids)`` with
    the same column-naming rule the full catalog join uses, so a delta batch
    joins byte-compatibly with its parent layout.
    """
    lk = np.asarray(fact_cols[left_key])
    rk = np.asarray(right[right_key])
    order = np.argsort(rk, kind="stable")
    rk_sorted = rk[order]
    pos = np.searchsorted(rk_sorted, lk)
    pos_clip = np.minimum(pos, len(rk_sorted) - 1)
    matched = rk_sorted[pos_clip] == lk
    fact_idx = np.nonzero(matched)[0]
    right_idx = order[pos_clip[fact_idx]]

    cols: Dict[str, Array] = {}
    fact_take = jnp.asarray(fact_idx)
    right_take = jnp.asarray(right_idx)
    for a in sorted(fact_cols):
        cols[a] = jnp.take(jnp.asarray(fact_cols[a]), fact_take, axis=0)
    for a in right.schema:
        name = a if a not in cols else f"{right.name}.{a}"
        cols[name] = jnp.take(right[a], right_take, axis=0)
    return cols, fact_idx, right_idx


class Catalog:
    """Cross-query cache of encodings, bucketizations, joins and instances.

    Every map is bounded FIFO (``max_entries`` per map): entries hold strong
    table references to keep their id() keys valid, so an unbounded cache
    would pin every table ever touched for the catalog's lifetime.  Replaced
    tables can be dropped eagerly with ``invalidate_table``.
    """

    def __init__(self, max_entries: int = 512):
        self.stats: collections.Counter = collections.Counter()
        self.max_entries = max_entries
        # All maps key by id() of the table(s) involved and keep a strong
        # reference to them, so ids stay valid while the entry lives.
        self._groups: Dict[Tuple[int, Tuple[str, ...]], Tuple[ColumnTable, GroupEncoding]] = {}
        self._buckets: Dict[Tuple[int, Tuple], Tuple[ColumnTable, Array]] = {}
        self._frag_sizes: Dict[Tuple[int, Tuple], Tuple[ColumnTable, np.ndarray]] = {}
        self._joins: Dict[Tuple[int, int, str, str], Tuple[ColumnTable, ColumnTable, ColumnTable, np.ndarray]] = {}
        self._instances: Dict[Tuple[int, int], Tuple[object, ColumnTable, ColumnTable]] = {}
        self._distinct: Dict[Tuple[int, str], Tuple[ColumnTable, int, np.ndarray]] = {}
        self._nonneg: Dict[Tuple[int, str], Tuple[ColumnTable, bool]] = {}
        self._wheres: Dict[Tuple[int, Tuple], Tuple[ColumnTable, Array]] = {}
        # GB fast-path fragment-of-group vectors, keyed by (uid, version,
        # group-by, partition) — *value* keys, not id(): the vector is a pure
        # function of the group dictionary (deterministic per table version)
        # and the partition bounds, so it survives re-samples and re-clusters.
        self._frag_groups: Dict[Tuple, np.ndarray] = {}
        # Instance -> (base table, base-row index per instance row): lets
        # ``groups``/``where_mask`` on a sketch instance gather from the base
        # table's cached products instead of fresh full host passes.
        self._instance_rows: Dict[int, Tuple[ColumnTable, ColumnTable, np.ndarray]] = {}
        # Padded sketch instance, or a join of one -> (table, real rows).
        self._valid_rows: Dict[int, Tuple[ColumnTable, int]] = {}
        # Stacked shard-major instances (``repro.core.shard``), keyed by
        # (registration key, table uid/version, plan identity) with a token
        # guard (per-shard table ids + sketch bits) so any shard-side delta
        # application or bit flip rebuilds the stack.  Values are opaque to
        # the catalog (a ``shard.StackedInstances``).
        self._stacked: Dict[Tuple, Tuple[Tuple, object]] = {}

    # The ``stats`` keys of lookups that did work instead of returning a
    # cached product: full builds, delta refreshes and instance derivations.
    MISS_STATS = (
        "encode_groups", "encode_groups_instance", "encode_groups_delta",
        "bucketize", "bucketize_delta", "frag_of_group",
        "fragment_sizes", "fragment_sizes_delta",
        "where_mask", "where_mask_instance", "where_mask_delta",
        "join_materialize", "join_delta", "join_instance", "instance_build",
        "stacked_build",
        "distinct_count", "distinct_count_delta", "column_stats", "column_stats_delta",
    )

    def misses(self) -> int:
        return sum(self.stats[k] for k in self.MISS_STATS)

    def clear(self) -> None:
        self.__init__(max_entries=self.max_entries)

    def _put(self, cache: Dict, key, value) -> None:
        if len(cache) >= self.max_entries:
            cache.pop(next(iter(cache)))  # FIFO eviction (insertion-ordered)
            self.stats["evictions"] += 1
        cache[key] = value

    def invalidate_table(self, table: ColumnTable) -> None:
        """Drop every entry keyed to ``table`` (it was replaced, e.g. by
        ``cluster_by``): id-guarded entries of a dead object can never hit
        again but would otherwise pin the old columns until evicted."""
        tid = id(table)
        for cache in (self._groups, self._buckets, self._frag_sizes,
                      self._distinct, self._nonneg, self._wheres):
            for k in [k for k in cache if k[0] == tid]:
                del cache[k]
        for k in [k for k in self._joins if tid in (k[0], k[1])]:
            del self._joins[k]
        for k in [k for k in self._instances if k[1] == tid]:
            del self._instances[k]
        for k in [k for k, v in self._instance_rows.items()
                  if k == tid or v[1] is table]:
            del self._instance_rows[k]
        self._valid_rows.pop(tid, None)

    def invalidate_chain(self, table: ColumnTable) -> None:
        """Invalidate ``table`` and every ancestor on its delta chain.

        The companion of ``ColumnTable.collapse``: id()-keyed entries hold
        strong table references, so without this the collapsed chain (every
        prior version's columns) would stay pinned until FIFO eviction.
        """
        t = table
        while t is not None:
            self.invalidate_table(t)
            t = t.delta.parent if t.delta is not None else None

    # -- stacked shard-major instances ---------------------------------------
    def get_stacked(self, key: Tuple, token: Tuple) -> Optional[object]:
        hit = self._stacked.get(key)
        if hit is not None and hit[0] == token:
            self.stats["stacked_hit"] += 1
            return hit[1]
        return None

    def put_stacked(self, key: Tuple, token: Tuple, value: object) -> None:
        self.stats["stacked_build"] += 1
        self._put(self._stacked, key, (token, value))

    def drop_stacked(self, key_prefix) -> None:
        """Drop stacked entries whose key starts with ``key_prefix`` (used
        when a registration is evicted so its stack stops pinning arrays)."""
        for k in [k for k in self._stacked if k[: len(key_prefix)] == key_prefix]:
            del self._stacked[k]

    # -- group-by dictionary encodings --------------------------------------
    def groups(self, table: ColumnTable, attrs: Tuple[str, ...]) -> GroupEncoding:
        key = (id(table), tuple(attrs))
        hit = self._groups.get(key)
        if hit is not None and hit[0] is table:
            self.stats["encode_groups_hit"] += 1
            return hit[1]
        parent = self._instance_parent(table) if attrs else None
        if parent is not None:
            # Sketch instance: derive from the base table's cached encoding
            # by a gather + dense renumber.  ``np.unique(axis=0)`` numbers
            # groups lexicographically, so restricting the base numbering to
            # the present groups (order-preserving) reproduces a from-scratch
            # encode of the instance bit-for-bit — in O(rows + groups)
            # instead of an O(n log n) host sort per instance.
            base, rows = parent
            base_enc = self.groups(base, attrs)
            gid_rows = base_enc.gid[rows]
            counts = np.bincount(gid_rows, minlength=base_enc.n_groups)
            present = counts > 0
            new_of_base = np.cumsum(present) - 1
            gid = new_of_base[gid_rows].astype(np.int32)
            n_groups = int(present.sum())
            group_values = {a: v[present] for a, v in base_enc.group_values.items()}
            enc = GroupEncoding(gid, jnp.asarray(gid), n_groups, group_values)
            self.stats["encode_groups_instance"] += 1
            self._put(self._groups, key, (table, enc))
            return enc
        d = table.delta
        if d is not None and attrs:
            parent = self.groups(d.parent, attrs)
            if d.kind == "append":
                enc = extend_encoding(parent, d.appended, tuple(attrs))
            else:
                gid = parent.gid[d.kept_idx].astype(np.int32)
                # Group numbering survives a delete; emptied groups simply
                # stop appearing (the executor's present-mask hides them).
                enc = GroupEncoding(gid, jnp.asarray(gid), parent.n_groups,
                                    parent.group_values, parent._key_index)
            self.stats["encode_groups_delta"] += 1
            self._put(self._groups, key, (table, enc))
            return enc
        self.stats["encode_groups"] += 1
        gid, n_groups, group_values = encode_groups(table, attrs)
        enc = GroupEncoding(gid=gid, gid_dev=jnp.asarray(gid), n_groups=n_groups,
                            group_values=group_values)
        self._put(self._groups, key, (table, enc))
        return enc

    # -- partition-attribute bucketizations ----------------------------------
    @staticmethod
    def _bucketize_raw(table: ColumnTable, ranges) -> Array:
        """Bucketize one table under a single-attribute or composite partition."""
        if hasattr(ranges, "parts"):  # CompositeRanges duck-type
            return ranges.bucketize(table)
        return ranges.bucketize(table[ranges.attr])

    def bucketize(self, table: ColumnTable, ranges: "RangeSet") -> Array:
        key = (id(table), ranges.key())
        hit = self._buckets.get(key)
        if hit is not None and hit[0] is table:
            self.stats["bucketize_hit"] += 1
            return hit[1]
        d = table.delta
        if d is not None:
            parent_bucket = self.bucketize(d.parent, ranges)
            if d.kind == "append":
                bucket = jnp.concatenate(
                    [parent_bucket, self._bucketize_raw(d.appended, ranges)])
            else:
                bucket = jnp.take(parent_bucket, jnp.asarray(d.kept_idx), axis=0)
            self.stats["bucketize_delta"] += 1
            self._put(self._buckets, key, (table, bucket))
            return bucket
        self.stats["bucketize"] += 1
        bucket = self._bucketize_raw(table, ranges)
        self._put(self._buckets, key, (table, bucket))
        return bucket

    def frag_of_group(
        self,
        table: ColumnTable,
        ranges: "RangeSet",
        groupby: Tuple[str, ...],
        group_values: Dict[str, np.ndarray],
    ) -> np.ndarray:
        """Fragment id per *group* under a partition on group-by attributes.

        The CB-OPT-GB fast path's vector: when every partition attribute is a
        group-by attribute the group key pins the fragment exactly, so the
        bucketization of the per-group key values answers incidence for every
        estimate over this (table version, group-by, partition) — cached here
        instead of re-bucketizing the group values on each estimate.
        Composite partitions assemble the row-major cross-product id.
        """
        key = (table.uid, table.version, tuple(groupby), ranges.key())
        hit = self._frag_groups.get(key)
        n_groups = len(next(iter(group_values.values()))) if group_values else 1
        if hit is not None and hit.shape[0] == n_groups:
            self.stats["frag_of_group_hit"] += 1
            return hit
        self.stats["frag_of_group"] += 1
        parts = getattr(ranges, "parts", (ranges,))
        frag = None
        for r in parts:
            b = np.asarray(r.bucketize(jnp.asarray(group_values[r.attr])))  # analyze: waive[SYNC01]: deliberate merge: fragment-of-group cache stores host arrays, computed once per (table, ranges)
            frag = b if frag is None else frag * r.n_ranges + b
        if len(self._frag_groups) >= self.max_entries:
            self._frag_groups.pop(next(iter(self._frag_groups)))
        self._frag_groups[key] = frag
        return frag

    def cached_bucket(self, table: ColumnTable, ranges: "RangeSet") -> Optional[Array]:
        """The full bucket vector iff it is available without full-table work.

        Returns the cached entry, or delta-refreshes it when every ancestor up
        to a cached entry is reachable through deltas; returns ``None`` when
        producing it would cost a full-table bucketize (callers then fall back
        to bucketizing just the rows they touch).
        """
        t = table
        while True:
            hit = self._buckets.get((id(t), ranges.key()))
            if hit is not None and hit[0] is t:
                return self.bucketize(table, ranges)  # delta-refresh the chain
            if t.delta is None:
                return None
            t = t.delta.parent

    def fragment_sizes(self, table: ColumnTable, ranges: "RangeSet") -> np.ndarray:
        key = (id(table), ranges.key())
        hit = self._frag_sizes.get(key)
        if hit is not None and hit[0] is table:
            self.stats["fragment_sizes_hit"] += 1
            return hit[1]
        d = table.delta
        if d is not None:
            parent_sizes = self.fragment_sizes(d.parent, ranges)
            if d.kind == "append":
                # Refresh the full bucket vector through the delta path: the
                # batch-sized tail feeds the counts here and the cached vector
                # is exactly what sketch application gathers from next.
                delta_bucket = np.asarray(
                    self.bucketize(table, ranges))[d.parent.num_rows:]
                sign = 1
            else:
                delta_bucket = np.asarray(self.bucketize(d.parent, ranges))[d.deleted_idx]
                sign = -1
            counts = np.bincount(delta_bucket, minlength=ranges.n_ranges)
            sizes = parent_sizes + sign * counts.astype(parent_sizes.dtype)
            self.stats["fragment_sizes_delta"] += 1
            self._put(self._frag_sizes, key, (table, sizes))
            return sizes
        self.stats["fragment_sizes"] += 1
        bucket = self.bucketize(table, ranges)
        sizes = np.asarray(  # analyze: waive[SYNC01]: deliberate merge: fragment-size histogram is cached as a host array, once per (table, ranges)
            jax.ops.segment_sum(
                jnp.ones_like(bucket, dtype=jnp.int32), bucket,
                num_segments=ranges.n_ranges,
            )
        )
        self._put(self._frag_sizes, key, (table, sizes))
        return sizes

    def frag_stats(self, table: ColumnTable, ranges: "RangeSet") -> Tuple[int, float, float]:
        """Summary statistics of a partition: ``(n_nonempty, max_frac,
        min_frac)`` over the nonempty fragments (fractions of table rows).

        The PS3-style pre-filter input: everything it needs to bound a
        candidate's sketch size comes from the cached per-fragment counts, so
        dominance pruning costs catalog metadata only — no sampling, no AQR
        pass, no estimate launch.  Delta-refreshed along with
        ``fragment_sizes``.
        """
        sizes = self.fragment_sizes(table, ranges)
        total = max(int(sizes.sum()), 1)
        nonempty = sizes[sizes > 0]
        if nonempty.size == 0:
            return (0, 0.0, 0.0)
        return (int(nonempty.size),
                float(nonempty.max()) / total,
                float(nonempty.min()) / total)

    # -- predicate-pushdown WHERE masks --------------------------------------
    def where_mask(self, table: ColumnTable, pred) -> Array:
        """The row mask of ``pred`` over ``table``, cached per (table version,
        predicate).

        ``pred`` is a ``queries.Predicate`` (duck-typed here — importing it
        would cycle).  Keys use object identity for the table (each version is
        a distinct object) plus the predicate's value tuple, and a miss on a
        delta-carrying version refreshes from the parent's mask: appends
        evaluate the predicate on the batch alone, deletes gather the kept
        rows — never a full-table re-evaluation.
        """
        key = (id(table), (pred.attr, pred.op, pred.value))
        hit = self._wheres.get(key)
        if hit is not None and hit[0] is table:
            self.stats["where_mask_hit"] += 1
            return hit[1]
        parent = self._instance_parent(table)
        if parent is not None:
            base, rows = parent
            mask = jnp.take(self.where_mask(base, pred), jnp.asarray(rows), axis=0)
            self.stats["where_mask_instance"] += 1
            self._put(self._wheres, key, (table, mask))
            return mask
        d = table.delta
        if d is not None:
            parent_mask = self.where_mask(d.parent, pred)
            if d.kind == "append":
                mask = jnp.concatenate([parent_mask, pred.mask(d.appended)])
            else:
                mask = jnp.take(parent_mask, jnp.asarray(d.kept_idx), axis=0)
            self.stats["where_mask_delta"] += 1
            self._put(self._wheres, key, (table, mask))
            return mask
        self.stats["where_mask"] += 1
        mask = pred.mask(table)
        self._put(self._wheres, key, (table, mask))
        return mask

    # -- join layouts ---------------------------------------------------------
    def join(
        self, fact: ColumnTable, right: ColumnTable, left_key: str, right_key: str
    ) -> Tuple[ColumnTable, np.ndarray]:
        """Materialized equi-join (right key unique) + fact-row back-map.

        Fact rows with no partner are dropped (inner join); right-side columns
        are prefixed with ``<right>.`` when their name collides.  A sketch
        instance whose base table's layout is cached joins by a gather of
        that layout (``_join_instance``).  Each build runs under a
        ``repro.join`` span and counts its rows and bytes into the current
        trace record.
        """
        hit = self._cached_join(fact, right, left_key, right_key)
        if hit is not None:
            self.stats["join_hit"] += 1
            return hit
        d = fact.delta
        parent = self._instance_parent(fact) if d is None else None
        layout = (None if parent is None
                  else self._cached_join(parent[0], right, left_key, right_key))
        if d is not None:
            p_joined, p_fact_idx = self.join(d.parent, right, left_key, right_key)
        with span("repro.join"):
            if d is not None:
                if d.kind == "append":
                    batch_cols = {a: np.asarray(d.appended[a]) for a in d.appended.schema}
                    cols_new, b_idx, _ = join_rows(batch_cols, right, left_key, right_key)
                    # Build the new joined table *as an append of its parent*,
                    # so the joined relation carries its own delta chain and
                    # its group encodings delta-refresh like base tables'.
                    joined = p_joined.append({a: cols_new[a] for a in p_joined.schema})
                    fact_idx = np.concatenate([p_fact_idx, b_idx + d.parent.num_rows])
                else:
                    keep_row = np.zeros(d.parent.num_rows, dtype=bool)
                    keep_row[d.kept_idx] = True
                    old_to_new = np.cumsum(keep_row) - 1
                    joined_keep = keep_row[p_fact_idx]
                    joined = p_joined.delete(~joined_keep)
                    fact_idx = old_to_new[p_fact_idx[joined_keep]]
                self.stats["join_delta"] += 1
            elif layout is not None:
                joined, fact_idx = self._join_instance(fact, *parent, *layout)
                self.stats["join_instance"] += 1
            else:
                cols, fact_idx, _ = join_rows(
                    {a: fact[a] for a in fact.schema}, right, left_key, right_key)
                joined = ColumnTable(f"{fact.name}_join_{right.name}", cols, fact.primary_key)
                self.stats["join_materialize"] += 1
        valid = self._valid_rows.get(id(fact))
        if valid is not None and valid[0] is fact:
            # The padding is a tail of the instance, and a join keeps its
            # rows in instance order.
            self.note_valid_rows(joined, int(np.count_nonzero(fact_idx < valid[1])))
        count(join_rows=joined.num_rows,
              join_bytes=sum(int(v.nbytes) for v in joined.columns.values()),
              join_derived=int(layout is not None))
        self._put(self._joins, (id(fact), id(right), left_key, right_key),
                  (fact, right, joined, fact_idx))
        return joined, fact_idx

    def _cached_join(
        self, fact: ColumnTable, right: ColumnTable, left_key: str, right_key: str
    ) -> Optional[Tuple[ColumnTable, np.ndarray]]:
        """``fact``'s cached join layout, or ``None`` (builds nothing)."""
        hit = self._joins.get((id(fact), id(right), left_key, right_key))
        if hit is not None and hit[0] is fact and hit[1] is right:
            return hit[2], hit[3]
        return None

    def _join_instance(
        self, instance: ColumnTable, base: ColumnTable, rows: np.ndarray,
        layout: ColumnTable, layout_fact_idx: np.ndarray,
    ) -> Tuple[ColumnTable, np.ndarray]:
        """The join of a sketch instance, gathered from its base table's
        layout: each instance row (padding included) whose base row has a
        partner, in instance order, as ``join_rows`` would produce them from
        scratch.  Instance-only columns (the padding's ``PAD_VALID``) come
        from the instance.  The result is registered as a subset of the
        layout, so its encodings and masks derive by a gather too."""
        at = np.full(base.num_rows, -1, dtype=np.int64)  # layout row of each base row
        at[layout_fact_idx] = np.arange(layout_fact_idx.shape[0])
        pos = at[rows]
        fact_idx = np.nonzero(pos >= 0)[0]
        take = pos[fact_idx]
        joined = layout.gather(jnp.asarray(take))
        fact_take = jnp.asarray(fact_idx)
        for a in instance.schema:
            if not joined.has(a):
                joined = joined.with_column(a, jnp.take(instance[a], fact_take, axis=0))
        self._put(self._instance_rows, id(joined), (joined, layout, take))
        return joined, fact_idx

    # -- sketch instances (D_P) ----------------------------------------------
    def get_instance(self, sketch: object, table: ColumnTable) -> Optional[ColumnTable]:
        key = (id(sketch), id(table))
        hit = self._instances.get(key)
        if hit is not None and hit[0] is sketch and hit[1] is table:
            self.stats["instance_hit"] += 1
            return hit[2]
        return None

    def put_instance(self, sketch: object, table: ColumnTable,
                     instance: ColumnTable, rows: Optional[np.ndarray] = None) -> None:
        self.stats["instance_build"] += 1
        self._put(self._instances, (id(sketch), id(table)), (sketch, table, instance))
        if rows is not None:
            # Remember the subset map so the instance's group encodings and
            # WHERE masks derive from the base table's cached ones by a
            # gather (``groups`` / ``where_mask`` consult this first).
            self._put(self._instance_rows, id(instance),
                      (instance, table, np.asarray(rows)))

    def note_valid_rows(self, table: ColumnTable, n: int) -> None:
        """Record that only the first ``n`` rows of ``table`` are real: the
        rest are padding (``PAD_VALID`` False)."""
        self._put(self._valid_rows, id(table), (table, n))

    def valid_rows(self, table: ColumnTable) -> int:
        """The rows of ``table`` that are not padding."""
        hit = self._valid_rows.get(id(table))
        return hit[1] if hit is not None and hit[0] is table else table.num_rows

    def _instance_parent(
        self, table: ColumnTable
    ) -> Optional[Tuple[ColumnTable, np.ndarray]]:
        hit = self._instance_rows.get(id(table))
        if hit is not None and hit[0] is table:
            return hit[1], hit[2]
        return None

    # -- cheap per-attribute statistics ---------------------------------------
    def distinct_count(self, table: ColumnTable, attr: str) -> int:
        key = (id(table), attr)
        hit = self._distinct.get(key)
        if hit is not None and hit[0] is table:
            return hit[1]
        d = table.delta
        if d is not None and d.kind == "append":
            parent_hit = self._distinct.get((id(d.parent), attr))
            if parent_hit is not None and parent_hit[0] is d.parent:
                uniq = np.union1d(parent_hit[2], np.asarray(d.appended[attr]))
                self.stats["distinct_count_delta"] += 1
                self._put(self._distinct, key, (table, int(uniq.shape[0]), uniq))
                return int(uniq.shape[0])
        # Deletes may or may not remove a value's last occurrence, so they
        # recompute; appends without a cached parent do too.
        self.stats["distinct_count"] += 1
        uniq = np.unique(np.asarray(table[attr]))
        self._put(self._distinct, key, (table, int(uniq.shape[0]), uniq))
        return int(uniq.shape[0])

    def column_nonnegative(self, table: ColumnTable, attr: str) -> bool:
        key = (id(table), attr)
        hit = self._nonneg.get(key)
        if hit is not None and hit[0] is table:
            return hit[1]
        d = table.delta
        if d is not None:
            parent_hit = self._nonneg.get((id(d.parent), attr))
            if parent_hit is not None and parent_hit[0] is d.parent:
                parent_ok = parent_hit[1]
                if d.kind == "append":
                    ok = parent_ok and not bool((np.asarray(d.appended[attr]) < 0).any())
                    self.stats["column_stats_delta"] += 1
                    self._put(self._nonneg, key, (table, ok))
                    return ok
                if parent_ok:  # removing rows cannot introduce negatives
                    self.stats["column_stats_delta"] += 1
                    self._put(self._nonneg, key, (table, True))
                    return True
        self.stats["column_stats"] += 1
        ok = not bool((np.asarray(table[attr]) < 0).any())
        self._put(self._nonneg, key, (table, ok))
        return ok


_DEFAULT = Catalog()


def default_catalog() -> Catalog:
    """Process-wide catalog used when callers don't thread their own."""
    return _DEFAULT
