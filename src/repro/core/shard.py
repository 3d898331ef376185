"""Fragment-sharded serving: place fragments across shards, route sketches.

The paper's payoff is that a captured sketch makes subsequent queries nearly
free by skipping non-provenance fragments.  Fragments are therefore the
natural unit of horizontal scale-out: a clustered ``ColumnTable``'s fragments
are placed across S shards (host-emulated shard objects by default, pinned to
a ``jax`` device mesh when one exists — see ``repro.parallel.placement``), and
a reused sketch is routed as a *fragment-id set* to only the shards owning set
bits.

Serving is SPMD by default: the contacted shards' local sketch instances are
kept as a *stacked shard-major* representation (``StackedInstances`` — rows
pow2-padded to a common count, stacked on a leading shard axis, group ids
rewritten into a coordinator-owned global dictionary), and ONE
``shard_map``/vmapped launch computes every shard's per-group partial
aggregates (sums + WHERE-passing counts) in a single XLA program, merging
them over the shard axis inside the launch.  ``ShardedEngine.run_batch``
extends the same launch with a leading query axis, so a whole hit batch —
even across different registered sketches — costs one program.  The
per-shard host loop (each shard's ``partial()`` evaluated separately, merged
by group key on the coordinator) survives behind ``fused=False`` — it is the
shape a real multi-process RPC deployment would take, and the benchmark
baseline.  Either way the query finishes with the same group-level code
single-node execution uses (``queries.result_from_group_state``), so routed
results match single-node results exactly whenever the aggregate arithmetic
is exact (integer-valued columns within float32 range — the same envelope
the maintenance subsystem pins, see
``SketchMaintainer._clears_trustworthy``).

Replication is delta-based, not state-based: ``append_rows``/``delete_rows``
are coordinator operations that route each batch by fragment ownership and
*ship* per-shard deltas into shard inboxes.  Shards advance independently —
each applies its pending deltas and advances its per-sketch maintainers the
next time it is read — and cross-shard reads gate on a minimum version
watermark (every contacted shard must have drained up to the coordinator's
mutation count) instead of a global lock.  Per-shard ``SketchMaintainer``s
behind one logical index entry keep the sketch's bits current shard-locally
whenever every group is shard-local (the placement attribute is part of the
(outer) GROUP BY, so a group's rows share one fragment and one owner); the
logical bits are then the OR of the shard bits.  For group-spanning queries
the HAVING chain needs global aggregates, so the coordinator's own maintainer
repairs the logical sketch (still delta-sized) and shards serve only the
routed partial aggregation.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Deque, Dict, List, Mapping, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.catalog import Catalog
from repro.core.engine import PBDSEngine, RunInfo
from repro.runtime import guards, trace
from repro.runtime.guards import hot_path
from repro.core.index import IndexEntry
from repro.core.maintenance import MaintenanceError, SketchMaintainer, maintainer_for
from repro.core.replication import ReplicationRecord
from repro.core.queries import (
    Query,
    QueryResult,
    inner_block_arrays,
    inner_group_partials,
    result_from_group_state,
)
from repro.core.ranges import RangeSet, equi_depth_ranges
from repro.core.table import ColumnTable, Database, FragmentLayout
from repro.parallel.placement import (
    failover_device,
    place_stacked,
    place_table,
    serving_mesh,
    shard_devices,
)
from repro.runtime.elastic import plan_replacement
from repro.runtime.resilience import RetryPolicy, StragglerMonitor, with_retries


class ShardUnavailableError(RuntimeError):
    """A shard could not be reached: dead, partitioned, or mid-failure.

    The retryable error class of the serving layer — ``ShardedEngine`` wraps
    every shard op in ``runtime.resilience.with_retries`` against exactly
    this type, so transient drops retry while logic errors (e.g. the
    mis-routed-tail corruption guard) surface immediately.
    """


class BackpressureError(RuntimeError):
    """A shard's inbox is at its depth cap; the coordinator must drain or
    let its per-shard delta log carry the entry until the next resync."""


class StaleEpochError(RuntimeError):
    """A shard rejected an op fenced behind the coordinator epoch it has
    seen.  Deliberately NOT a ``ShardUnavailableError``: the serving layer
    must never retry a fenced-out coordinator's op — the op is invalid, not
    transient, and the only correct reaction is to stop acting as the
    coordinator (a newer one has taken over)."""


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Fragment -> shard ownership map for one range partition."""

    n_shards: int
    owner: np.ndarray  # (n_fragments,) shard id per fragment

    def fragments_of(self, shard_id: int) -> np.ndarray:
        return np.nonzero(self.owner == shard_id)[0]

    def shards_for(self, frag_ids: np.ndarray) -> np.ndarray:
        """The distinct shards owning any of ``frag_ids`` — the route set."""
        return np.unique(self.owner[np.asarray(frag_ids)])


def plan_fragments(
    sizes: np.ndarray, n_shards: int, policy: str = "contig"
) -> ShardPlan:
    """Place fragments on shards.

    ``contig`` (default) cuts the fragment sequence into row-balanced
    contiguous runs, preserving range locality — a selective sketch's bits
    are usually clustered in value space, so contiguous ownership maximizes
    fully-skipped shards.  ``spread`` round-robins fragments, trading
    locality for uniform load under adversarial (striped) sketches.
    """
    sizes = np.asarray(sizes, dtype=np.float64)
    n_frags = sizes.shape[0]
    owner = np.zeros(n_frags, dtype=np.int64)
    if policy == "spread":
        owner = np.arange(n_frags, dtype=np.int64) % n_shards
    elif policy == "contig":
        per = sizes.sum() / max(n_shards, 1)
        s, load = 0, 0.0
        for f in range(n_frags):
            if s < n_shards - 1 and load >= per:
                s, load = s + 1, 0.0
            owner[f] = s
            load += sizes[f]
    else:
        raise ValueError(f"unknown placement policy {policy!r}")
    return ShardPlan(n_shards=n_shards, owner=owner)


def local_table_for(
    shard_id: int,
    plan: ShardPlan,
    ranges: RangeSet,
    clustered: ColumnTable,
    version: int = 0,
) -> ColumnTable:
    """Gather ``shard_id``'s owned rows out of the coordinator's clustered
    table into a shard-local clustered layout.

    Factored out of ``FragmentShard.__init__`` so the peer-checkpoint path
    can derive the exact same local table on the coordinator and ship it to
    a *peer* shard process — recovery then pulls shard-sized state from the
    peer instead of re-shipping the full table from the coordinator.
    """
    if clustered.layout is None:
        raise ValueError("shards are built from a clustered table")
    owned = plan.fragments_of(shard_id)
    lay = clustered.layout
    off = lay.offsets
    parts = [np.arange(off[f], off[f + 1]) for f in owned]
    n_tail_local = 0
    if lay.tail:
        # Rebuild-from-coordinator path (failover/rebalance): the source
        # table may carry an unsorted append tail — route its rows by
        # fragment ownership exactly like ``ShardedEngine.append_rows``.
        n = clustered.num_rows
        tail_vals = np.asarray(clustered[ranges.attr])[n - lay.tail:]
        tail_frag = np.asarray(ranges.bucketize(jnp.asarray(tail_vals)))  # analyze: waive[SYNC01]: recovery/rebuild path (shard construction), not a serving hot path — tail routing needs host fragment ids
        own_tail = (n - lay.tail) + np.nonzero(
            plan.owner[tail_frag] == shard_id)[0]
        n_tail_local = int(own_tail.shape[0])
        parts.append(own_tail)
    idx = np.concatenate(parts) if parts else np.empty(0, dtype=np.int64)
    local = clustered.gather(jnp.asarray(idx))
    local_sizes = np.array([off[f + 1] - off[f] for f in owned],
                           dtype=np.int64)
    layout = FragmentLayout(
        attr=ranges.attr,
        # Never collides with a RangeSet.key(): local fragment ids are a
        # different coordinate system from the global partition's.
        ranges_key=("shard", shard_id, ranges.key()),
        offsets=np.concatenate([[0], np.cumsum(local_sizes)]).astype(np.int64),
        tail=n_tail_local,
    )
    return ColumnTable(local.name, local.columns, clustered.primary_key,
                       layout, version=version)


class FragmentShard:
    """One shard: its owned fragments' rows, catalog, and sketch maintainers.

    The local table is clustered over the *owned* fragments (local fragment j
    is the j-th owned global fragment, ascending), with appended rows landing
    in the layout's unsorted tail exactly like a single-node clustered table.
    Deltas arrive through ``ship`` into an inbox and are applied lazily by
    ``catch_up`` — the emulation of asynchronous replication.
    """

    MAX_DELTA_CHAIN = 16

    def __init__(
        self,
        shard_id: int,
        plan: ShardPlan,
        ranges: RangeSet,
        clustered: ColumnTable,
        dims: Mapping[str, ColumnTable],
        device=None,
        inbox_cap: Optional[int] = None,
        version: int = 0,
        local_table: Optional[ColumnTable] = None,
    ):
        self.shard_id = shard_id
        self.ranges = ranges
        self.owned = plan.fragments_of(shard_id)
        # global fragment id -> local fragment position (-1 = not owned).
        self._local_of_global = np.full(ranges.n_ranges, -1, dtype=np.int64)
        self._local_of_global[self.owned] = np.arange(self.owned.shape[0])

        if local_table is None:
            local_table = local_table_for(shard_id, plan, ranges, clustered,
                                          version=version)
        self.device = device
        self.table: Optional[ColumnTable] = place_table(local_table, device)
        self.dims: Dict[str, ColumnTable] = {
            k: place_table(v, device) for k, v in dims.items()}
        self.catalog = Catalog()
        self.maintainers: Dict[int, SketchMaintainer] = {}
        self._inst: Dict[int, Tuple[Tuple, ColumnTable]] = {}
        self._inbox: Deque[Tuple[int, str, object]] = collections.deque()
        # Inbox depth cap: a shard that never drains (dead, partitioned)
        # must not silently eat the coordinator's memory — past the cap
        # ``ship`` raises ``BackpressureError`` and the coordinator's delta
        # log carries the entry until the next resync.
        self.inbox_cap = inbox_cap
        self.backpressure_hits = 0
        # Fault-injection state (``runtime.chaos`` drives it): the guard
        # below is the in-process stand-in for an RPC boundary.
        self.fault: Optional[str] = None  # None|"dead"|"stall"|"partition"|"flaky"
        self.stall_s = 0.0
        self._flaky_fails = 0
        # Highest coordinator epoch this shard has accepted an op from.
        # Survives kills/rebuilds of the shard's *state* — it is process
        # identity, not table state — so a fenced-out coordinator stays
        # fenced out even across shard recovery.
        self.epoch = 0

    @classmethod
    def from_local(
        cls,
        shard_id: int,
        plan: ShardPlan,
        ranges: RangeSet,
        local_table: ColumnTable,
        dims: Mapping[str, ColumnTable],
        device=None,
        inbox_cap: Optional[int] = None,
    ) -> "FragmentShard":
        """Build a shard directly from an already-local table (peer-replicated
        checkpoint recovery) — no coordinator-table gather, no full reship."""
        return cls(shard_id, plan, ranges, clustered=None, dims=dims,
                   device=device, inbox_cap=inbox_cap,
                   local_table=local_table)

    # -- epoch fencing ---------------------------------------------------------
    def fence(self, epoch: int, op: str = "") -> None:
        """Reject ops fenced behind the newest coordinator epoch seen.

        Monotone max: a newer coordinator's first op bumps the shard's epoch,
        after which every op from the old (possibly partitioned) coordinator
        raises ``StaleEpochError`` — zombie mutations cannot land.
        """
        if epoch < self.epoch:
            raise StaleEpochError(
                f"shard {self.shard_id}: coordinator epoch {epoch} is fenced "
                f"behind {self.epoch} ({op or 'op'})")
        self.epoch = epoch

    # -- fault injection -------------------------------------------------------
    def _guard(self, op: str) -> None:
        """Every shard op passes through here — the failure choke point."""
        if self.fault in ("dead", "partition"):
            raise ShardUnavailableError(
                f"shard {self.shard_id} is {self.fault} ({op})")
        if self.fault == "flaky":
            self._flaky_fails -= 1
            if self._flaky_fails <= 0:
                self.fault = None
            raise ShardUnavailableError(
                f"shard {self.shard_id} dropped {op} (flaky)")
        if self.fault == "stall" and self.stall_s > 0:
            time.sleep(self.stall_s)
        if self.table is None:
            raise ShardUnavailableError(
                f"shard {self.shard_id} lost its state ({op})")

    def inject(self, kind: str, arg=None) -> None:
        """Inject one fault.  ``kill`` loses ALL in-memory state — table,
        maintainers, caches, inbox — exactly like a process death; ``stall``
        makes every op sleep (a straggler); ``partition`` makes the shard
        unreachable with state intact; ``flaky`` fails the next ``arg`` ops
        then self-heals (exercises the retry path)."""
        if kind == "kill":
            self.fault = "dead"
            self.table = None
            self.maintainers.clear()
            self._inst.clear()
            self._inbox.clear()
            self.catalog = Catalog()
        elif kind == "stall":
            self.fault = "stall"
            self.stall_s = float(arg) if arg is not None else 0.02
        elif kind == "partition":
            self.fault = "partition"
        elif kind == "flaky":
            self.fault = "flaky"
            self._flaky_fails = int(arg) if arg is not None else 1
        else:
            raise ValueError(f"unknown fault kind {kind!r}")

    def heal(self) -> None:
        """Clear any injected fault.  A killed shard becomes *reachable but
        empty* — the coordinator detects the lost state on its next read and
        runs checkpoint-adopt + delta-replay + re-registration recovery."""
        self.fault = None
        self.stall_s = 0.0
        self._flaky_fails = 0

    @property
    def reachable(self) -> bool:
        """Can the coordinator talk to this shard at all right now?"""
        return self.fault not in ("dead", "partition")

    def adopt(self, table: ColumnTable, dims: Mapping[str, ColumnTable]) -> None:
        """Install recovered state (checkpoint table + current dims) after a
        kill; maintainers and caches are gone until re-registration."""
        self.table = place_table(table, self.device)
        self.dims = {k: place_table(v, self.device) for k, v in dims.items()}
        self.catalog = Catalog()
        self.maintainers = {}
        self._inst = {}
        self._inbox.clear()

    # -- replication -----------------------------------------------------------
    @property
    def version(self) -> int:
        """Local watermark: how many fact-table deltas have been applied
        (``-1`` while the shard's state is lost)."""
        return self.table.version if self.table is not None else -1

    @property
    def lag(self) -> int:
        return len(self._inbox)

    def ship(self, version: int, kind: str, payload) -> None:
        """Enqueue one versioned delta (``append`` row batch / ``delete``
        local mask).  Delivery is idempotent — ``catch_up`` drops entries at
        or below the local version — so the coordinator may re-ship a log
        suffix after a partition without coordination."""
        self._guard("ship")
        if self.inbox_cap is not None and len(self._inbox) >= self.inbox_cap:
            self.backpressure_hits += 1
            raise BackpressureError(
                f"shard {self.shard_id} inbox at cap ({self.inbox_cap})")
        self._inbox.append((version, kind, payload))

    def update_dim(self, table: ColumnTable) -> None:
        """Replace a replicated dimension table (applied eagerly — dimension
        mutations are rare and invalidate join maintainers wholesale)."""
        self._guard("update_dim")
        old = self.dims.get(table.name)
        if old is not None:
            self.catalog.invalidate_table(old)
        self.dims[table.name] = place_table(table, self.device)
        for key in [k for k, m in self.maintainers.items()
                    if m.q.join is not None and m.q.join.right == table.name]:
            del self.maintainers[key]

    def _db(self) -> Database:
        tables = dict(self.dims)
        tables[self.table.name] = self.table
        return Database(tables)

    def catch_up(self, watermark: int) -> int:
        """Drain pending deltas up to ``watermark``; advance maintainers.

        Returns the number of deltas applied.  Work is delta-sized: the
        table grows/shrinks by the batch, maintainers re-count only the
        batch rows, and catalog entries refresh through the delta chain.
        A maintainer that cannot advance (e.g. its dimension table was
        replaced mid-chain) is dropped; the coordinator re-registers it
        from scratch on the next read that needs it.  Duplicate inbox
        entries (version at or below the local watermark — resync re-ships)
        are dropped; a version *gap* (a ship lost to backpressure or a
        partition) stops the drain so the coordinator can resync the
        missing suffix from its delta log.
        """
        self._guard("catch_up")
        applied = 0
        while self.table.version < watermark and self._inbox:
            version, kind, payload = self._inbox[0]
            if version <= self.table.version:
                self._inbox.popleft()  # duplicate re-ship: idempotent skip
                continue
            if version > self.table.version + 1:
                break  # gap: wait for the coordinator's log resync
            self._inbox.popleft()
            if kind == "append":
                self.table = self.table.append(payload)
            elif kind == "delete":
                self.table = self.table.delete(payload)
            else:  # pragma: no cover - defensive
                raise ValueError(f"unknown delta kind {kind!r}")
            applied += 1
        if applied:
            db = self._db()
            for key, m in list(self.maintainers.items()):
                try:
                    m.apply(self.table, db)
                except MaintenanceError:
                    del self.maintainers[key]
            self._inst.clear()
        if self.table.delta_depth() > self.MAX_DELTA_CHAIN:
            self.catalog.invalidate_chain(self.table)
            self.table = self.table.collapse()
        return applied

    # -- sketch registration ---------------------------------------------------
    def register(self, key: int, q: Query, ranges: RangeSet) -> None:
        """Build this shard's maintainer for one logical index entry.

        The shard must be at the coordinator's watermark (the maintainer
        counts the *current* local rows).  Registration waves sharing an
        inner-block signature (batched admission, recovery re-registration)
        pay ONE local counting pass and clone the rest.
        """
        self._guard("register")
        self.maintainers[key] = maintainer_for(
            q, self._db(), ranges, self.catalog,
            list(self.maintainers.values()))

    def unregister(self, key: int) -> None:
        self.maintainers.pop(key, None)
        self._inst.pop(key, None)

    def bits_for(self, key: int) -> Optional[np.ndarray]:
        """This shard's maintained sketch bits (global fragment ids), or
        ``None`` when the maintainer was lost and needs re-registration."""
        self._guard("bits_for")
        m = self.maintainers.get(key)
        return m.bits() if m is not None else None

    # -- query serving ---------------------------------------------------------
    def _instance(self, key: int, ranges: RangeSet, bits: np.ndarray) -> ColumnTable:
        """The local sketch instance: owned ∩ sketch fragments (+ tail filter).

        When the sketch's partition is the serving partition this is pure
        slice concatenation over the local fragment-major layout; any other
        partition falls back to the per-row keep-mask over local rows.
        """
        self._guard("instance")
        token = (id(self.table), bits.tobytes())
        cached = self._inst.get(key)
        if cached is not None and cached[0] == token:
            self.catalog.stats["instance_hit"] += 1
            return cached[1]
        lay = self.table.layout
        if ranges.key() == self.ranges.key():
            local_ids = np.nonzero(bits[self.owned])[0]
            tail_bucket = None
            if lay.tail:
                gfrag = np.asarray(self.catalog.bucketize(self.table, self.ranges))  # analyze: waive[SYNC01]: deliberate merge: instance build (registration-time) maps global fragments to shard-local ids on host
                tail_bucket = self._local_of_global[
                    gfrag[self.table.num_rows - lay.tail:]]
                if tail_bucket.size and tail_bucket.min() < 0:
                    # A tail row bucketized to a fragment this shard does not
                    # own: routing and bucketization disagree — corruption.
                    raise RuntimeError(
                        f"shard {self.shard_id}: mis-routed tail rows "
                        f"(fragments {np.unique(gfrag[self.table.num_rows - lay.tail:][tail_bucket < 0])})")
            inst = self.table.take_fragments(local_ids, tail_bucket=tail_bucket)
            self.catalog.stats["instance_slices"] += 1
        else:
            bucket = self.catalog.bucketize(self.table, ranges)
            inst = self.table.select(jnp.asarray(bits)[bucket])
            self.catalog.stats["instance_mask"] += 1
        self._inst[key] = (token, inst)
        return inst

    def partial(
        self, q: Query, key: int, ranges: RangeSet, bits: np.ndarray
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        """Per-group partial aggregates of the inner block over the local
        sketch instance: ``(group key values, sums, WHERE-passing counts)``.

        Group ids are shard-local; the coordinator re-keys on the group
        *values* when merging, so numbering never has to be coordinated.
        """
        inst = self._instance(key, ranges, bits)
        if q.join is not None:
            flat, _ = self.catalog.join(
                inst, self.dims[q.join.right], q.join.left_key, q.join.right_key)
        else:
            flat = inst
        enc, _, sums, counts = inner_group_partials(q, flat, self.catalog)
        return enc.group_values, np.asarray(sums), np.asarray(counts)


# ---------------------------------------------------------------------------
# Stacked shard-major execution (the fused SPMD hot path)
# ---------------------------------------------------------------------------

# Telemetry for the fused launch: ``TRACE_COUNTS`` bumps at trace time only
# (tests assert pow2 quantization keeps shard-count / sketch-set changes in
# one compiled size class), ``LAUNCH_COUNTS`` bumps once per host-side
# invocation (tests assert the hit path costs exactly one launch per batch).
# Both live in the shared ``runtime.guards`` registry (keys owned here);
# the module-level names stay for callers/tests addressing them as
# ``shard.TRACE_COUNTS``.
TRACE_COUNTS: collections.Counter = guards.TRACE_COUNTS
LAUNCH_COUNTS: collections.Counter = guards.LAUNCH_COUNTS


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


@dataclasses.dataclass(frozen=True)
class StackedInstances:
    """Shard-major stacked inner-block arrays for one registered entry.

    Per-shard sketch-instance rows (post-join flat tables) are padded to a
    common pow2 row count and stacked on a leading shard axis — values, group
    ids (in the coordinator-owned *global* group dictionary), and weights
    (WHERE ∧ row-validity; padded rows carry weight 0, the ``__valid__``
    convention of pow2-padded instances).  All three carry a leading
    query axis of 1 so a batch of hits concatenates without reshapes.  The
    shard axis is pow2-padded too, and placed over the 1-D serving mesh when
    one exists, so one ``shard_map``/vmapped launch computes every shard's
    per-group partials in a single XLA program.
    """

    vals: jax.Array  # (1, S_pad, R_pad) f32
    gid: jax.Array  # (1, S_pad, R_pad) i32 — global group ids
    weights: jax.Array  # (1, S_pad, R_pad) f32 — WHERE ∧ valid
    n_groups: int
    g_pad: int
    group_values: Dict[str, np.ndarray]  # global dictionary (np.unique order)
    contacted_ids: Tuple[int, ...]  # shard ids owning >= 1 sketch fragment
    token: Tuple = ()  # freshness token (shard table versions + sketch bits)

    @property
    def contacted(self) -> int:
        return len(self.contacted_ids)

    @property
    def r_pad(self) -> int:
        return int(self.vals.shape[2])


def _fused_body(vals, gid, w, g_pad: int):
    """(K, S, R) stacked arrays -> (K, g_pad) merged per-group sums/counts.

    One program: each query's shard slices flatten into one row axis (the
    shard-axis reduction IS the segment sum — group ids are already global),
    so the batched segment-aggregate kernel runs with batch = the query axis
    only.  f32 sums of integral values are exact under any association, so
    the result is bit-identical to the host-loop per-shard-partial merge and
    to single-node execution (the envelope ``tests/test_shard.py`` pins).
    """
    TRACE_COUNTS["fused_partials"] += 1
    from repro.kernels import ops as kops

    k, s, r = vals.shape
    return kops.segment_aggregate_batch(
        vals.reshape(k, s * r), gid.reshape(k, s * r), g_pad,
        w.reshape(k, s * r))


_fused_jit = functools.partial(jax.jit, static_argnums=(3,))(_fused_body)

# mesh id -> (mesh, jitted shard_map fn); the mesh reference keeps the id valid.
_SPMD_FNS: Dict[int, Tuple[object, object]] = {}


def _spmd_body(vals, gid, w, g_pad: int):
    """Per-device block of the shard_map launch: each device reduces its
    local shard slices into (K, g_pad) partial matrices, psum merges."""
    TRACE_COUNTS["fused_partials"] += 1
    from repro.kernels import ops as kops

    k, s, r = vals.shape
    sums, counts = kops.segment_aggregate_batch(
        vals.reshape(k, s * r), gid.reshape(k, s * r), g_pad,
        w.reshape(k, s * r))
    return jax.lax.psum(sums, "shards"), jax.lax.psum(counts, "shards")


def _fused_spmd_fn(mesh):
    """The jitted shard_map launch for one mesh (cached per mesh)."""
    hit = _SPMD_FNS.get(id(mesh))
    if hit is not None:
        return hit[1]
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.jit, static_argnums=(3,))
    def fn(vals, gid, w, g_pad):
        body = jax.shard_map(
            functools.partial(_spmd_body, g_pad=g_pad),
            mesh=mesh,
            in_specs=(P(None, "shards", None),) * 3,
            out_specs=(P(None, None), P(None, None)),
            # The body's Pallas kernel declares no per-axis variance on its
            # outputs; the psum makes the results replicated either way.
            check_vma=False,
        )
        return body(vals, gid, w)

    _SPMD_FNS[id(mesh)] = (mesh, fn)
    return fn


# ---------------------------------------------------------------------------
# Coordinator
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Registered:
    """Routed-serving state for one logical index entry.

    ``group_local`` selects the bits source: per-shard maintainers when every
    group is shard-local, the coordinator's maintainer otherwise.
    Registration state is keyed by ``entry.reg_id`` — a stable counter the
    coordinator assigns at admission and replicates, so a standby
    coordinator's rebuilt entries re-attach to the maintainers the shard
    processes already hold without re-registration.
    """

    entry: IndexEntry
    ranges: RangeSet
    group_local: bool


@dataclasses.dataclass
class RouteInfo:
    """Bookkeeping of one routed (reused-sketch) execution or hit batch."""

    contacted: int
    skipped: int
    watermark: int
    deltas_applied: int
    per_shard_s: Dict[int, float]
    t_merge_s: float
    # Device-launch wall time: the single stacked program on the fused path,
    # the summed per-shard ``partial()`` calls on the host-loop path.
    t_launch_s: float = 0.0
    # True when served by the stacked one-launch SPMD path, False for the
    # per-shard host loop.
    fused: bool = False
    # Devices the fused launch's stacked inputs span (0 for the host loop).
    launch_devices: int = 0
    # Queries served by this route (one, or a run_batch hit batch).
    n_queries: int = 1
    # Degraded-mode bookkeeping: ``failed_shards`` lists the shards whose
    # fragment slices were served from the coordinator's authoritative table
    # this route (down, partitioned, or past the op deadline), ``n_retries``
    # the transient shard-op failures retried away by ``with_retries``.
    degraded: bool = False
    failed_shards: Tuple[int, ...] = ()
    n_retries: int = 0
    # Cumulative count of peer-mirror refreshes that could not land (peer
    # dead/backpressured): while nonzero-and-growing, a shard kill may pay a
    # full coordinator-checkpoint re-ship instead of the delta-sized peer
    # restore.  Surfaced so operators see silent staleness, not just feel it.
    stale_checkpoints: int = 0

    @property
    def t_critical_s(self) -> float:
        """Emulated shard-parallel latency.  Host-loop: slowest contacted
        shard + merge (host-emulated shards run sequentially; real
        deployments overlap).  Fused: the one launch already computes all
        shards in a single program, so launch + merge IS the critical path."""
        if self.fused:
            return self.t_launch_s + self.t_merge_s
        return (max(self.per_shard_s.values()) if self.per_shard_s else 0.0) \
            + self.t_merge_s


class ShardedEngine:
    """Coordinator: a ``PBDSEngine`` for selection/capture plus S fragment
    shards for serving.

    The coordinator keeps the authoritative table (captures, candidate
    selection, and NO-PS fallbacks run single-node over it); index *hits* are
    served routed: per-shard maintained bits are OR-merged into the logical
    sketch, only shards owning set bits are contacted, and their per-group
    partials are merged into the final result.  Mutations ship per-shard
    deltas and return immediately; shards drain on their next read.
    """

    def __init__(
        self,
        db: Database,
        table: str,
        attr: str,
        n_shards: int,
        n_ranges: int = 64,
        strategy: str = "CB-OPT-GB",
        policy: str = "contig",
        use_devices: bool = True,
        fused: bool = True,
        max_registered: Optional[int] = None,
        health: bool = True,
        op_deadline_s: float = 5.0,
        inbox_cap: Optional[int] = 4096,
        retry_policy: Optional[RetryPolicy] = None,
        transport: str = "loopback",
        epoch: int = 0,
        _boot: Optional[Mapping] = None,
        **engine_kwargs,
    ):
        for k in ("cluster_tables", "compact_tail_frac"):
            if k in engine_kwargs:
                # Physical re-permutes of the coordinator table would desync
                # the global-row -> shard-row map that delete routing needs.
                raise ValueError(f"{k} is coordinator-managed in ShardedEngine")
        boot = dict(_boot or {})
        self.table_name = table
        self.attr = attr
        self.n_shards = n_shards
        if "ranges" in boot:
            # Takeover path (``from_replica``): the partition, clustering and
            # placement are *adopted* from the replicated bootstrap, never
            # re-derived — re-deriving them on the post-mutation table would
            # silently re-fragment and orphan every registered sketch.
            self.ranges = boot["ranges"]
            clustered = boot["clustered"]
        else:
            self.ranges = equi_depth_ranges(db[table], attr, n_ranges)
            clustered = db[table].cluster_by(self.ranges)
        self.engine = PBDSEngine(
            db.with_table(clustered), strategy=strategy, n_ranges=n_ranges,
            **engine_kwargs)
        # The serving partition IS the engine's partition for ``attr``, so a
        # sketch selected on it routes as fragment slices on every shard.
        self.engine._ranges_cache[(table, attr)] = self.ranges
        if "owner" in boot:
            self.plan = ShardPlan(n_shards=n_shards,
                                  owner=np.asarray(boot["owner"]))
        else:
            self.plan = plan_fragments(
                np.diff(clustered.layout.offsets), n_shards, policy=policy)
        self.policy = policy
        self.use_devices = use_devices
        self._engine_kwargs = dict(engine_kwargs)
        self._n_ranges = n_ranges
        dims = {k: v for k, v in self.engine.db.tables.items() if k != table}
        self._devices = shard_devices(n_shards, use_devices)
        self._inbox_cap = inbox_cap
        # Shard surface: every shard op goes through a ShardClient.  The
        # loopback backend wraps in-process FragmentShards (zero-copy,
        # today's behavior); the subprocess backend runs each shard as a
        # separate OS process behind a socket RPC channel — same failure
        # vocabulary (ShardUnavailableError / BackpressureError), so the
        # health machine and degraded routing below are backend-blind.
        from repro.core import shard_rpc  # deferred: shard_rpc imports us

        self.transport = transport
        # Coordinator epoch: carried on every shard op; shards fence out any
        # lower epoch (see ``FragmentShard.fence``), so a superseded
        # coordinator cannot land zombie mutations after a takeover.
        self.epoch = int(epoch)
        if boot.get("attach") is not None:
            # Takeover re-attach: wrap the *live* shard transports (loopback
            # FragmentShards / subprocess server sockets) in fresh clients
            # owned by this coordinator — no shard state moves.
            self.shards = [c.clone_for_takeover() for c in boot["attach"]]
        elif transport == "loopback":
            self.shards = [
                shard_rpc.LoopbackShardClient(
                    FragmentShard(s, self.plan, self.ranges, clustered, dims,
                                  self._devices[s], inbox_cap=inbox_cap))
                for s in range(n_shards)
            ]
        elif transport == "subprocess":
            self.shards = [
                shard_rpc.SubprocessShardClient(
                    s, self.plan, self.ranges, clustered, dims,
                    inbox_cap=inbox_cap, op_deadline_s=op_deadline_s)
                for s in range(n_shards)
            ]
        else:
            raise ValueError(f"unknown transport {transport!r}")
        for c in self.shards:
            c.epoch = self.epoch
        # Global-row -> (shard, local-row) map, maintained across mutations so
        # coordinator delete masks translate to shard-local masks.
        if boot:
            # The adopted table may carry an unsorted append tail; the full
            # recompute routes it by ownership like ``append_rows`` did.
            self._rebuild_row_maps()
        else:
            n = clustered.num_rows
            frag_of_row = np.searchsorted(
                clustered.layout.offsets, np.arange(n), side="right") - 1
            self._row_shard = self.plan.owner[frag_of_row]
            self._row_local = np.empty(n, dtype=np.int64)
            self._shard_rows = np.zeros(n_shards, dtype=np.int64)
            for s in range(n_shards):
                sel = self._row_shard == s
                self._shard_rows[s] = int(sel.sum())
                self._row_local[sel] = np.arange(self._shard_rows[s])
        # Coordinator mutation count == the read watermark.
        self.version = int(boot.get("version", 0))
        # reg_id -> routed-serving state for that logical entry.
        self._registered: Dict[int, _Registered] = {}
        # Monotone registration-id source; replicated so a standby keeps
        # minting ids the shards have never seen.
        self._reg_counter = int(boot.get("reg_counter", 1))
        # -- metadata replication (``core/replication``): every metadata
        # mutation is streamed as a monotonically-sequenced record to the
        # attached replica.  ``None`` = not replicating; a publish failure
        # degrades (drops the replica) but never takes down serving.
        self._replica = None
        self._rep_seq = 0
        self.replica_degraded = False
        # -- peer-replicated checkpoints (subprocess transport): shard
        # ``sid``'s local table is mirrored on shard ``(sid+1) % S``'s server
        # process and kept current by the same per-shard deltas ``_ship``
        # already produces, so recovery of a killed server pulls shard-sized
        # state from the peer instead of re-shipping the full table.
        self._peer_mirroring = (transport == "subprocess" and n_shards > 1)
        self._peer_ok = [False] * n_shards
        self.peer_restores = 0
        # Stale-checkpoint signal (satellite): a checkpoint or peer mirror
        # that could not advance past a failure is *counted*, not silently
        # left behind; ``RouteInfo.stale_checkpoints`` surfaces the total.
        self.stale_checkpoints = [0] * n_shards
        self.last_route: Optional[RouteInfo] = None
        # Fused SPMD serving: stacked one-launch execution (the default);
        # ``fused=False`` keeps the per-shard host loop (benchmark baseline,
        # and the only path real multi-process RPC shards could take today).
        self.fused = fused
        self._mesh = serving_mesh(use_devices)
        self._launch_devices = 0
        # Per-shard memory bound: registrations beyond this are pruned by the
        # coordinator's recency clock (``SketchIndex.prune``) after each
        # registration pass, evicting shard maintainers + cached instances.
        self.max_registered = max_registered
        # -- shard health tracking (healthy -> suspect -> dead -> recovering
        # -> healthy).  ``health=False`` bypasses the per-op wrapper entirely
        # (fault-free benchmarking baseline: quantifies the tracking layer's
        # overhead; never run it against a chaotic cluster).
        self.health_tracking = health
        self.op_deadline_s = op_deadline_s
        self._retry_policy = retry_policy or RetryPolicy(
            max_attempts=3, backoff_s=1e-3, backoff_mult=2.0,
            retryable=(ShardUnavailableError,), deadline_s=op_deadline_s)
        self.health: List[str] = ["healthy"] * n_shards
        self._monitors: Dict[Tuple[int, str], StragglerMonitor] = {}
        self._route_retries = 0
        # Coordinator-durable recovery state: per-shard checkpoint (loopback:
        # a reference to the shard's immutable local table as of its last
        # fully drained read; subprocess: the coordinator's clustered table
        # at the watermark, from which the shard rebuilds server-side) plus
        # the delta log of everything shipped past it.  Recovery of a lost
        # shard is checkpoint-restore + delta-replay + maintainer
        # re-registration — never a from-scratch re-capture.
        if boot.get("attach") is not None:
            # Live shards keep their state; the replicated delta-log suffix
            # covers anything a shard has not yet drained or ever received.
            self._ckpt = []
            for c in self.shards:
                try:
                    self._ckpt.append(
                        None if c.state_lost
                        else c.make_checkpoint(self.db[table], self.version))
                except ShardUnavailableError:
                    self._ckpt.append(None)
            logs = boot.get("log") or [[] for _ in range(n_shards)]
            self._log = [list(entries) for entries in logs]
        else:
            self._ckpt = [c.make_checkpoint(clustered, 0) for c in self.shards]
            self._log = [[] for _ in range(n_shards)]

    # -- metadata replication / standby takeover -------------------------------
    def _emit(self, kind: str, payload) -> None:
        if self._replica is None:
            return
        self._rep_seq += 1
        try:
            self._replica.publish(ReplicationRecord(self._rep_seq, kind,
                                                    payload))
        except Exception:
            # Replica loss degrades replication, never serving: the active
            # coordinator keeps answering queries without a standby.
            self._replica = None
            self.replica_degraded = True

    def _plan_token(self) -> int:
        """Fingerprint of the current placement; stamps peer-mirrored
        checkpoints so a mirror built under a pre-rebalance plan can never
        be adopted under the new one."""
        return hash((self.n_shards, self.plan.owner.tobytes()))

    def _reg_payloads(self, entries: Sequence[IndexEntry]) -> List[dict]:
        out = []
        for e in entries:
            reg = self._registered.get(e.reg_id)
            m = e.maintainer
            out.append({
                "reg_id": e.reg_id,
                "query": e.query,
                "ranges": reg.ranges if reg is not None else e.sketch.ranges,
                "registered": reg is not None,
                "group_local": (reg.group_local if reg is not None else False),
                # Counter state rides along (miss-path only — registration is
                # already a capture) so takeover restores the maintainer
                # instead of paying the per-sketch group re-encode.
                "state": (m.state_dict()
                          if isinstance(m, SketchMaintainer) else None),
            })
        return out

    def _boot_payload(self) -> dict:
        dims = {k: v.collapse() for k, v in self.engine.db.tables.items()
                if k != self.table_name}
        return {
            "table": self.table_name,
            "attr": self.attr,
            "n_shards": self.n_shards,
            "n_ranges": self._n_ranges,
            "strategy": self.engine.strategy,
            "policy": self.policy,
            "use_devices": self.use_devices,
            "fused": self.fused,
            "max_registered": self.max_registered,
            "health": self.health_tracking,
            "op_deadline_s": self.op_deadline_s,
            "inbox_cap": self._inbox_cap,
            "transport": self.transport,
            "engine_kwargs": dict(self._engine_kwargs),
            "ranges": self.ranges,
            "owner": np.asarray(self.plan.owner),
            "clustered": self.db[self.table_name].collapse(),
            "dims": dims,
            "version": self.version,
            "log": [list(entries) for entries in self._log],
            "ckpt_versions": [None if c is None else c.version
                              for c in self._ckpt],
            "reg_counter": self._reg_counter,
            "selection": self.selection_state(),
            "epoch": self.epoch,
        }

    def attach_replica(self, replica) -> None:
        """Start streaming metadata mutations to ``replica`` (warm standby).

        Emits a full bootstrap — base state, current delta logs, every live
        registration, the selection snapshot — so a standby attached mid-life
        (or re-armed by a freshly-promoted coordinator) holds everything;
        every later metadata mutation streams as its own sequenced record.
        """
        self._replica = replica
        self._rep_seq = 0
        self.replica_degraded = False
        self._emit("bootstrap", self._boot_payload())
        regs = self._reg_payloads(
            [e for e in self.engine.index.entries() if e.reg_id > 0])
        if regs:
            self._emit("register", regs)

    @classmethod
    def from_replica(cls, store, *, epoch: int,
                     attach: Optional[Sequence] = None) -> "ShardedEngine":
        """Standby takeover: rebuild a serving coordinator from replicated
        metadata alone.

        * The clustered base table + the replicated mutation log replay to
          the exact coordinator table (same row order, same (uid, version)
          lineage — so shard-side freshness tokens keep matching).
        * Partition, placement and delta-log suffixes are adopted, never
          re-derived.
        * Index entries rebuild **locally** under their replicated
          ``reg_id``s — replicated maintainer counter state restores via
          ``SketchMaintainer.from_state`` + delta replay (falling back to an
          eager ``maintainer_for`` counting pass when the state is stale or
          unwalkable) — the shards' maintainers are still keyed by those
          ids, so hits stay hits with zero re-registration RPCs and
          ``index.misses`` stays flat: no re-capture, ever.
        * ``attach`` re-wraps the *live* shard transports; no full-table
          reship to any shard that still has its state.

        The caller owns fencing: construct with a bumped ``epoch``, then the
        first catch-up round stamps it onto every shard.
        """
        b = store.boot
        if b is None:
            raise RuntimeError("replica has no bootstrap record")
        table_name = b["table"]
        fact = b["clustered"]
        dims = dict(b["dims"])
        for mkind, tname, payload, _v in store.muts:
            if tname == table_name:
                fact = (fact.append(payload) if mkind == "append"
                        else fact.delete(payload))
                if fact.delta_depth() >= 64:
                    fact = fact.collapse()
            else:
                t = dims[tname]
                dims[tname] = (t.append(payload) if mkind == "append"
                               else t.delete(payload))
        db = Database({table_name: fact, **dims})
        self = cls(
            db, table_name, b["attr"], b["n_shards"],
            n_ranges=b["n_ranges"], strategy=b["strategy"],
            policy=b["policy"], use_devices=b["use_devices"],
            fused=b["fused"], max_registered=b["max_registered"],
            health=b["health"], op_deadline_s=b["op_deadline_s"],
            inbox_cap=b["inbox_cap"], transport=b["transport"],
            epoch=epoch,
            _boot=dict(
                ranges=b["ranges"], clustered=fact, owner=store.owner,
                attach=attach, version=store.version,
                log=store.ship_logs(b["n_shards"]),
                reg_counter=store.reg_counter),
            **b["engine_kwargs"],
        )
        if store.selection:
            self.restore_selection_state(store.selection)
        catalog = self.engine.catalog
        pool: List[SketchMaintainer] = []
        for rid, p in store.regs.items():
            q, ranges = p["query"], p["ranges"]
            m = None
            state = p.get("state")
            if state is not None:
                # Fast path: resurrect the replicated counter state and
                # delta-replay it to the current version — skips the
                # per-sketch group re-encode, which dominates takeover cost.
                try:
                    m = SketchMaintainer.from_state(
                        q, self.engine.db, ranges, state)
                    m.apply(self.engine.db[q.table], self.engine.db)
                except MaintenanceError:
                    m = None  # stale/unwalkable state: rebuild eagerly below
            try:
                if m is None:
                    m = maintainer_for(q, self.engine.db, ranges, catalog,
                                       pool)
                sketch = m.to_sketch(self.engine.db[q.table], catalog)
            except MaintenanceError:
                continue  # unrebuildable under the current tables: drop it
            pool.append(m)
            e = self.engine.index.insert(q, sketch, maintainer=m)
            e.reg_id = rid
            self.engine._ranges_cache.setdefault((q.table, ranges.attr),
                                                 ranges)
            if p["registered"]:
                self._registered[rid] = _Registered(e, ranges,
                                                    p["group_local"])
        return self

    # -- convenience -----------------------------------------------------------
    @property
    def db(self) -> Database:
        return self.engine.db

    @property
    def index(self):
        return self.engine.index

    def min_watermark(self) -> int:
        """The slowest shard's applied-delta count (monitoring hook)."""
        return min((s.version for s in self.shards), default=self.version)

    # -- mutations -------------------------------------------------------------
    def append_rows(self, table_name: str, rows: Mapping[str, np.ndarray]) -> None:
        """Route the batch by fragment ownership and ship per-shard deltas.

        Every shard receives a delta (possibly empty) so shard versions stay
        aligned with the coordinator's watermark; application is lazy.
        """
        if table_name != self.table_name:
            self.engine.append_rows(table_name, rows)
            self._emit("mutation", ("append", table_name,
                                    {k: np.asarray(v) for k, v in rows.items()},
                                    None, None))
            self._replicate_dim(table_name)
            return
        rows_np = {k: np.asarray(v) for k, v in rows.items()}
        # Route through RangeSet.bucketize itself so coordinator routing and
        # shard-side re-bucketization agree bit-for-bit on boundary values.
        bucket = np.asarray(self.ranges.bucketize(jnp.asarray(rows_np[self.attr])))
        shard_of = self.plan.owner[bucket]
        counts = np.bincount(shard_of, minlength=self.n_shards)
        new_local = np.empty(shard_of.shape[0], dtype=np.int64)
        version = self.version + 1
        ships = []
        for s, shard in enumerate(self.shards):
            sel = shard_of == s
            payload = {k: v[sel] for k, v in rows_np.items()}
            self._ship(s, version, "append", payload)
            ships.append(payload)
            new_local[sel] = self._shard_rows[s] + np.arange(counts[s])
        self._shard_rows += counts
        self._row_shard = np.concatenate([self._row_shard, shard_of])
        self._row_local = np.concatenate([self._row_local, new_local])
        self.engine.append_rows(table_name, rows)
        self.version += 1
        self._emit("mutation", ("append", table_name, rows_np, version, ships))

    def delete_rows(self, table_name: str, mask: np.ndarray) -> None:
        """Translate the coordinator-row mask into per-shard local masks."""
        if table_name != self.table_name:
            self.engine.delete_rows(table_name, mask)
            self._emit("mutation", ("delete", table_name,
                                    np.asarray(mask, dtype=bool), None, None))
            self._replicate_dim(table_name)
            return
        mask = np.asarray(mask, dtype=bool)
        version = self.version + 1
        ships = []
        for s, shard in enumerate(self.shards):
            local_mask = np.zeros(self._shard_rows[s], dtype=bool)
            local_mask[self._row_local[mask & (self._row_shard == s)]] = True
            self._ship(s, version, "delete", local_mask)
            ships.append(local_mask)
        keep = ~mask
        self._row_shard = self._row_shard[keep]
        self._row_local = self._row_local[keep]
        self._shard_rows = np.bincount(self._row_shard, minlength=self.n_shards)
        for s in range(self.n_shards):
            sel = self._row_shard == s
            self._row_local[sel] = np.arange(self._shard_rows[s])
        self.engine.delete_rows(table_name, mask)
        self.version += 1
        self._emit("mutation", ("delete", table_name, mask, version, ships))

    def _ship(self, sid: int, version: int, kind: str, payload) -> None:
        """Best-effort delivery of one delta.  The coordinator's per-shard
        delta log is the authoritative copy (appended first, pruned at
        checkpoints), so a failed or backpressured ship just leaves the
        shard lagging until the next read resyncs it from the log."""
        self._log[sid].append((version, kind, payload))
        if self._peer_mirroring:
            self._peer_ship(sid, version, kind, payload)
        if self.health_tracking and self.health[sid] == "dead":
            return  # known-dead: don't even try; recovery replays the log
        try:
            self.shards[sid].ship(version, kind, payload)
        except BackpressureError:
            pass  # inbox full; the log carries it
        except ShardUnavailableError:
            if self.health_tracking:
                self.health[sid] = ("dead" if self.health[sid] == "suspect"
                                    else "suspect")

    def _peer_ship(self, sid: int, version: int, kind: str, payload) -> None:
        """Keep shard ``sid``'s peer mirror current with the same delta.
        A failed or refused peer ship marks the mirror stale — counted, not
        silent — and the next checkpoint round re-seeds it."""
        if not self._peer_ok[sid]:
            return
        peer = (sid + 1) % self.n_shards
        try:
            ok = self.shards[peer].peer_ship(sid, version, kind, payload)
        except (ShardUnavailableError, BackpressureError):
            ok = False
        if not ok:
            self._peer_ok[sid] = False
            self.stale_checkpoints[sid] += 1

    def _replicate_dim(self, table_name: str) -> None:
        """Replicate a mutated dimension table and evict sketches it serves.

        A join sketch's provenance depends on the dimension contents, but
        sketches are versioned against the *fact* table only — serving one
        across a dimension mutation could silently return a stale-join
        result.  Eviction forces a fresh capture on the next miss.
        Unreachable shards are skipped — ``_sync_shard`` re-replicates any
        dimension whose (uid, version) drifted before the shard serves again.
        """
        for sid, shard in enumerate(self.shards):
            if self.health_tracking and self.health[sid] == "dead":
                continue
            try:
                shard.update_dim(self.engine.db[table_name])
            except ShardUnavailableError:
                if self.health_tracking:
                    self.health[sid] = ("dead" if self.health[sid] == "suspect"
                                        else "suspect")
        for e in list(self.engine.index.entries()):
            if e.query.join is not None and e.query.join.right == table_name:
                self.engine.index.remove(e)
                if e.reg_id:
                    self._unregister(e.reg_id)
                    self._emit("evict", e.reg_id)

    # -- queries ---------------------------------------------------------------
    @hot_path
    def run(self, q: Query) -> Tuple[QueryResult, RunInfo]:
        t0 = time.perf_counter()
        entry = (self.engine.index.lookup_entry(q)
                 if self.engine.strategy != "NO-PS" else None)
        if entry is not None:
            routed = self._run_routed(q, entry, t0)
            if routed is not None:
                return routed
        # Miss (or unroutable hit): single-node path on the coordinator, then
        # register any fresh capture with every shard.
        res, info = self.engine.run(q)
        self._register_new()
        return res, info

    def _group_local(self, q: Query) -> bool:
        """May sketch bits be maintained shard-locally for ``q``?

        A shard's maintainer evaluates the HAVING chain on *local* per-group
        aggregates, which equals the global evaluation only when every group
        (and, for nested templates, every outer group) lives entirely on one
        shard — i.e. the placement attribute is part of the (outer) GROUP BY,
        so a group's rows all share one fragment and hence one owner.
        """
        if self.attr not in q.groupby:
            return False
        if q.outer_groupby is not None and self.attr not in q.outer_groupby:
            return False
        return True

    def _register_new(self) -> None:
        """Broadcast every not-yet-registered index entry to the shards.

        One pass: the watermark catch-up runs once across all shards (not
        once per entry), then every new entry's per-shard maintainers are
        registered — the path ``run_batch`` uses to register a whole admitted
        wave's captures at once.
        """
        if self.engine.strategy == "NO-PS":
            return
        new = [e for e in self.engine.index.entries() if e.reg_id == 0]
        if not new:
            return
        for e in new:
            # Stable registration ids: shard maintainers, replication records
            # and routed-serving state all key on ``reg_id`` — a standby's
            # rebuilt entries re-attach to shard state without any
            # re-registration RPCs (``id(entry)`` dies with the process).
            e.reg_id = self._reg_counter
            self._reg_counter += 1
        fact_new = [e for e in new if e.query.table == self.table_name]
        down: Set[int] = set()
        if any(self._group_local(e.query) for e in fact_new):
            _, down = self._catch_up_all()
        for e in fact_new:
            group_local = self._group_local(e.query)
            if group_local:
                for sid, shard in enumerate(self.shards):
                    if sid in down or (self.health_tracking
                                       and self.health[sid] != "healthy"):
                        continue  # registered at recovery (_reregister_shard)
                    try:
                        self._shard_call(
                            sid, "register",
                            functools.partial(shard.register, e.reg_id,
                                              e.query, e.sketch.ranges))
                    except ShardUnavailableError:
                        pass
            self._registered[e.reg_id] = _Registered(e, e.sketch.ranges,
                                                     group_local)
        if self._replica is not None:
            self._emit("register", self._reg_payloads(new))
            self._emit("selection", self.selection_state())
        if self.max_registered is not None:
            self.prune(self.max_registered)

    def _unregister(self, key: int) -> None:
        for shard in self.shards:
            shard.unregister(key)
        self._registered.pop(key, None)
        self.engine.catalog.drop_stacked(("stacked", key))

    def prune(self, max_entries: int) -> int:
        """Bound per-shard memory with the coordinator's recency clock.

        Evicts least-recently-hit sketches from the coordinator index
        (``SketchIndex.prune``) and drops every evicted entry's shard-side
        state in the same pass: per-shard maintainers, cached local
        instances, and the stacked launch arrays.  Returns #evictions.
        """
        before = ({e.reg_id for e in self.engine.index.entries() if e.reg_id}
                  if self._replica is not None else set())
        evicted = self.engine.index.prune(max_entries)
        if evicted:
            alive = {e.reg_id for e in self.engine.index.entries()}
            for key in [k for k in self._registered if k not in alive]:
                self._unregister(key)
            for rid in sorted(before - alive):
                self._emit("evict", rid)
        return evicted

    def shutdown(self) -> None:
        """Release shard resources: loopback clients no-op, subprocess
        clients return their server process to the warm pool (or reap it).
        Safe to call more than once."""
        for c in self.shards:
            try:
                c.close_client()
            except Exception:
                pass

    # -- coordinator selection-state checkpointing ----------------------------
    def selection_state(self) -> dict:
        """The coordinator's reuse-aware selection state (WorkloadLog window
        + SelectionCache stats), picklable — shards never see it, so ONE
        log survives a coordinator restart even when shards are separate
        processes."""
        return self.engine.selection_state()

    def restore_selection_state(self, state: Mapping) -> None:
        self.engine.restore_selection_state(state)

    # -- health tracking / failover -------------------------------------------
    def _shard_call(self, sid: int, op: str, fn):
        """One guarded shard op: bounded retries with backoff + a deadline
        (``runtime.resilience.with_retries``), per-(shard, op) straggler
        tracking, and the shard state machine transitions.  A hard failure
        demotes healthy -> suspect -> dead; a clean in-deadline op promotes
        suspect/recovering -> healthy."""
        if not self.health_tracking:
            return fn()
        if self.health[sid] == "dead":
            raise ShardUnavailableError(f"shard {sid} marked dead")
        retries = 0

        def _count(_attempt: int, _e: Exception) -> None:
            nonlocal retries
            retries += 1

        t0 = time.perf_counter()
        try:
            out = with_retries(fn, self._retry_policy, on_retry=_count)
        except ShardUnavailableError:
            self._route_retries += retries
            self.health[sid] = ("dead" if self.health[sid] == "suspect"
                                else "suspect")
            raise
        dt = time.perf_counter() - t0
        self._route_retries += retries
        mon = self._monitors.get((sid, op))
        if mon is None:
            mon = self._monitors[(sid, op)] = StragglerMonitor()
        mon.observe(dt)
        if dt > self.op_deadline_s and mon.median() is not None:
            # Past the deadline (an injected stall, or a genuinely slow
            # host): route around it — its slices serve coordinator-side
            # until it answers within the deadline again.  The monitor's
            # warmup window grants grace while the op's timing baseline
            # forms (first calls pay one-time XLA compiles; demoting on
            # those would degrade perfectly healthy shards).
            self.health[sid] = "suspect"
        elif self.health[sid] in ("suspect", "recovering"):
            self.health[sid] = "healthy"
        return out

    def _checkpoint(self, sid: int) -> None:
        """Advance one shard's durable recovery point.  Called only when the
        shard is at version parity with the coordinator, so both checkpoint
        kinds (loopback: shard-table reference; subprocess: coordinator-table
        snapshot) are one immutable reference + a log prune.  Skips entirely
        when the checkpoint is already at the watermark — the warm read path
        pays a version compare, nothing else."""
        cur = self._ckpt[sid]
        if cur is not None and cur.version == self.version:
            self._mirror_ckpt(sid)
            return
        ckpt = self.shards[sid].make_checkpoint(
            self.db[self.table_name], self.version)
        self._ckpt[sid] = ckpt
        v = ckpt.version
        if self._log[sid] and self._log[sid][0][0] <= v:
            self._log[sid] = [e for e in self._log[sid] if e[0] > v]
        self._emit("ckpt", (sid, v))
        self._mirror_ckpt(sid)

    def _mirror_ckpt(self, sid: int) -> None:
        """(Re)seed shard ``sid``'s peer mirror when it is stale: derive the
        shard-local table coordinator-side (``local_table_for`` — the same
        pure gather shard construction uses) and put it on the peer.  Once
        seeded, ``_peer_ship`` keeps it current delta-sized."""
        if not self._peer_mirroring or self._peer_ok[sid]:
            return
        peer = (sid + 1) % self.n_shards
        if self.health_tracking and self.health[peer] == "dead":
            self.stale_checkpoints[sid] += 1
            return
        try:
            local = local_table_for(sid, self.plan, self.ranges,
                                    self.db[self.table_name].collapse(),
                                    version=self.version)
            self.shards[peer].peer_put(sid, local, self._plan_token())
            self._peer_ok[sid] = True
        except (ShardUnavailableError, BackpressureError):
            self.stale_checkpoints[sid] += 1

    def _restore_from_peer(self, sid: int) -> bool:
        """Recovery fast path: re-seed a killed shard from the peer-held
        mirror of its local table instead of re-shipping the coordinator
        checkpoint.  The mirror is delta-maintained, so the shipped bytes are
        O(shard-local rows) held *by the peer process* — the coordinator
        never serializes the table.  Tried regardless of ``_peer_ok``: the
        flag is this coordinator's knowledge, but mirrors survive coordinator
        takeover (they live in shard processes), so a fresh coordinator asks
        first and trusts the plan token to reject stale placements."""
        if not self._peer_mirroring:
            return False
        peer = (sid + 1) % self.n_shards
        if self.health_tracking and self.health[peer] == "dead":
            return False
        try:
            got = self.shards[peer].peer_fetch(sid, self._plan_token())
            if got is None:
                return False
            local, _version = got
            dims = {k: v for k, v in self.engine.db.tables.items()
                    if k != self.table_name}
            self.shards[sid].build_local(self.plan, self.ranges, local, dims,
                                         self._inbox_cap)
        except (ShardUnavailableError, BackpressureError):
            return False
        self.peer_restores += 1
        self._peer_ok[sid] = True
        return True

    def _sync_shard(self, sid: int) -> int:
        """Bring one shard to the coordinator watermark: refresh drifted
        dimension replicas, drain the inbox, and re-ship any log suffix the
        shard is missing (ships lost to a partition or to backpressure)."""
        shard = self.shards[sid]
        for name, t in self.engine.db.tables.items():
            if name == self.table_name:
                continue
            if shard.dim_token(name) != (t.uid, t.version):
                shard.update_dim(t)
        applied = shard.catch_up(self.version)
        while shard.version < self.version:
            missing = [e for e in self._log[sid] if e[0] > shard.version]
            if not missing:
                # The log cannot reach the watermark (pruned past a loss):
                # rebuild outright from the coordinator's table.
                return applied + self._rebuild_shard(sid)
            before = shard.version
            for entry in missing:
                try:
                    shard.ship(*entry)
                except BackpressureError:
                    break  # drain below, then ship the rest
            applied += shard.catch_up(self.version)
            if shard.version == before:
                # No progress: a version gap the log cannot bridge (e.g. it
                # was voided by a rebalance).  Rebuild outright.
                return applied + self._rebuild_shard(sid)
        return applied

    def _recover_shard(self, sid: int) -> int:
        """Failover recovery of a reachable-again shard: adopt the last
        checkpoint (state-lost kill), replay the delta log up to the
        watermark, re-register per-shard maintainers.  Delta-replay +
        re-registration — never a from-scratch re-capture: the maintainers
        re-count only the shard's local rows and the sketch *bits* come back
        through the same counting scheme that produced them."""
        shard = self.shards[sid]
        self.health[sid] = "recovering"
        applied = 0
        if shard.state_lost:  # killed: all local state lost
            if not self._restore_from_peer(sid):
                if self._ckpt[sid] is None:
                    # No coherent checkpoint (placement changed while it was
                    # gone): rebuild from the coordinator's table outright.
                    self._rebuild_shard(sid)
                    self.health[sid] = "healthy"
                    return 0
                dims = {k: v for k, v in self.engine.db.tables.items()
                        if k != self.table_name}
                shard.restore_checkpoint(self._ckpt[sid], dims, self.plan,
                                         self.ranges)
        applied += self._sync_shard(sid)
        self._reregister_shard(sid)
        self._checkpoint(sid)
        self.health[sid] = "healthy"
        return applied

    def _reregister_shard(self, sid: int) -> None:
        """Re-register every routed entry's per-shard maintainer after the
        shard's maintainer set was lost (kill) or rebuilt (rebalance)."""
        shard = self.shards[sid]
        for key, reg in self._registered.items():
            if not reg.group_local or not self.engine.index.contains(reg.entry):
                continue
            if not shard.has_maintainer(key):
                shard.register(key, reg.entry.query, reg.ranges)

    def _rebuild_shard(self, sid: int) -> int:
        """Rebuild one shard outright from the coordinator's authoritative
        clustered table per the current plan (O(local rows) gather) — the
        path elastic rebalancing takes, and the recovery fallback when the
        delta log cannot reach the watermark.  Still not a re-capture:
        maintainers re-register by local counting."""
        ctable = self.db[self.table_name]
        dims = {k: v for k, v in self.engine.db.tables.items()
                if k != self.table_name}
        dead = [s for s, h in enumerate(self.health) if h == "dead"]
        self._devices[sid] = failover_device(self._devices, sid, dead)
        self.shards[sid].rebuild(
            self.plan, self.ranges, ctable, dims, self._devices[sid],
            self._inbox_cap, self.version)
        self._log[sid] = []
        self._reregister_shard(sid)
        self._checkpoint(sid)
        return 0

    def _rebuild_row_maps(self) -> None:
        """Recompute the global-row -> (shard, local-row) maps from the
        coordinator table and the current plan (after a re-placement)."""
        ctable = self.db[self.table_name]
        lay = ctable.layout
        n = ctable.num_rows
        n_tail = lay.tail
        frag_prefix = np.searchsorted(lay.offsets, np.arange(n - n_tail),
                                      side="right") - 1
        if n_tail:
            tail_vals = np.asarray(ctable[self.attr])[n - n_tail:]
            tail_frag = np.asarray(self.ranges.bucketize(jnp.asarray(tail_vals)))
            row_frag = np.concatenate([frag_prefix, tail_frag])
        else:
            row_frag = frag_prefix
        self._row_shard = self.plan.owner[row_frag]
        self._row_local = np.empty(n, dtype=np.int64)
        self._shard_rows = np.zeros(self.n_shards, dtype=np.int64)
        for s in range(self.n_shards):
            sel = self._row_shard == s
            self._shard_rows[s] = int(sel.sum())
            self._row_local[sel] = np.arange(self._shard_rows[s])

    def rebalance(self, dead: Optional[Sequence[int]] = None) -> List[int]:
        """Elastic failover: re-plan fragment placement away from ``dead``
        shards (default: every shard currently marked dead) via the pure
        ``runtime.elastic.plan_replacement`` policy and rebuild the shards
        whose owned fragment set changed.  Returns the rebuilt shard ids."""
        if dead is None:
            dead = [s for s in range(self.n_shards)
                    if self.health[s] == "dead"]
        dead_set = {int(d) for d in dead}
        if not dead_set:
            return []
        sizes = np.diff(self.db[self.table_name].layout.offsets)
        new_owner = plan_replacement(sizes, self.plan.owner, self.n_shards,
                                     sorted(dead_set))
        changed = [s for s in range(self.n_shards)
                   if not np.array_equal(np.nonzero(new_owner == s)[0],
                                         self.plan.fragments_of(s))]
        self.plan = ShardPlan(n_shards=self.n_shards, owner=new_owner)
        self._rebuild_row_maps()
        # Every peer mirror speaks the OLD placement: the plan token embedded
        # at put-time no longer matches, so fetches would be refused anyway —
        # drop our seeded flags so the next checkpoint round re-seeds.
        self._peer_ok = [False] * self.n_shards
        rebuilt = []
        voided = []
        for sid in changed:
            if sid in dead_set:
                # The lost shard now owns nothing; void its recovery state —
                # checkpoint AND log speak the old placement, so a later
                # rejoin must rebuild from the coordinator, never replay.
                self._ckpt[sid] = None
                self._log[sid] = []
                voided.append(sid)
                continue
            self._rebuild_shard(sid)
            self.health[sid] = "healthy"
            rebuilt.append(sid)
        self._emit("plan", (new_owner, voided))
        # The plan object changed identity: every stacked cache key is dead.
        self.engine.catalog.drop_stacked(("stacked",))
        self.engine.catalog.drop_stacked(("stacked_batch",))
        return rebuilt

    def _catch_up_all(self) -> Tuple[int, Set[int]]:
        """Watermark gate, fault-tolerant: every reachable shard drains its
        inbox up to the coordinator's mutation count before serving — an
        un-contacted lagging shard could own fragments the mutations just
        made provenance-bearing (and its data must be current for
        partials).  Shards that cannot be brought current are returned as
        ``down``: their fragment slices serve from the coordinator's
        authoritative table this route (degraded mode).  Dead shards are
        probed each read; a reachable-again one runs checkpoint + delta-log
        recovery on the spot."""
        applied = 0
        down: Set[int] = set()
        for sid, shard in enumerate(self.shards):
            if self.health_tracking and self.health[sid] == "dead":
                if shard.reachable:
                    try:
                        applied += self._recover_shard(sid)
                    except (ShardUnavailableError, BackpressureError):
                        self.health[sid] = "dead"
                        down.add(sid)
                else:
                    down.add(sid)
                continue
            if shard.state_lost and shard.reachable:
                # Healed after a kill without ever being demoted to dead (no
                # serve happened in between): recover on the spot instead of
                # burning a serve discovering the loss through a failing
                # catch_up.
                try:
                    applied += self._recover_shard(sid)
                except (ShardUnavailableError, BackpressureError):
                    self.health[sid] = "dead"
                    down.add(sid)
                continue
            try:
                applied += self._shard_call(
                    sid, "catch_up", functools.partial(self._sync_shard, sid))
            except (ShardUnavailableError, BackpressureError):
                down.add(sid)
                continue
            shard = self.shards[sid]  # _sync_shard may have rebuilt it
            if shard.version < self.version:  # pragma: no cover - defensive
                down.add(sid)
            else:
                self._checkpoint(sid)
                if self.health_tracking and self.health[sid] == "healthy":
                    # A shard that sat out a registration wave (suspect at
                    # the time) picks up its missing maintainers the first
                    # read after it is healthy again.
                    try:
                        self._reregister_shard(sid)
                    except (ShardUnavailableError, BackpressureError):
                        down.add(sid)
        return applied, down

    def _degraded_set(self, down: Set[int]) -> Set[int]:
        """The shards served coordinator-side this route: unrecoverable
        (``down``) plus any flagged suspect by the op wrapper (stalled past
        the deadline, or one hard failure away from dead).  Shards owning no
        fragments (re-placed away by a rebalance) are excluded — they have
        nothing to substitute, so their state cannot degrade a route."""
        degraded = set(down)
        if self.health_tracking:
            degraded |= {s for s in range(self.n_shards)
                         if self.health[s] in ("suspect", "dead")}
        return {s for s in degraded if self.plan.fragments_of(s).size > 0}

    def _resolve_bits(
        self, key: int, reg: _Registered, degraded: Set[int]
    ) -> Optional[np.ndarray]:
        """The logical sketch bits for one registered entry (or ``None`` when
        a shard maintainer was lost — caller falls back to the miss path).

        Degraded shards are never contacted: the coordinator's own maintainer
        substitutes (``_current_sketch`` maintains or re-captures the logical
        sketch).  For group-local entries the coordinator bits equal the OR
        of per-shard bits — shard-locality of every group makes the local
        HAVING evaluations exactly the global one — so the substitution is
        bit-identical, not merely safe."""
        if reg.group_local:
            # Fully decentralized maintenance: every group is shard-local,
            # so the logical bits are the OR of per-shard maintained bits.
            bits_parts = []
            for sid, shard in enumerate(self.shards):
                if self.plan.fragments_of(sid).size == 0:
                    continue  # owns nothing (re-placed away): no bits to OR
                if sid in degraded:
                    bits_parts = None
                    break
                try:
                    b = self._shard_call(
                        sid, "bits_for", functools.partial(shard.bits_for, key))
                except ShardUnavailableError:
                    degraded.add(sid)
                    bits_parts = None
                    break
                if b is None:  # maintainer lost (e.g. dimension replaced)
                    self._unregister(key)
                    return None
                bits_parts.append(b)
            if bits_parts is not None:
                return np.logical_or.reduce(bits_parts)
        # Groups span shards (or a shard is degraded): the HAVING chain needs
        # global aggregates, so the *coordinator's* maintainer repairs the
        # logical sketch (delta-sized) and shards only serve routed partials.
        sketch, _ = self.engine._current_sketch(reg.entry)
        return sketch.bits

    # -- degraded-mode serving -------------------------------------------------
    def _degraded_flat(
        self, sid: int, q: Query, reg: _Registered, bits: np.ndarray
    ) -> ColumnTable:
        """Shard ``sid``'s sketch-instance slice served *coordinator-side*
        from the authoritative clustered table — the degraded-mode stand-in
        while the shard is down or lagging.  Row set matches the shard's own
        instance exactly; row order may differ, which is invisible under the
        exactness envelope (order-insensitive sums, value-keyed groups)."""
        ctable = self.db[self.table_name]
        ranges = reg.ranges
        owned = self.plan.fragments_of(sid)
        if ranges.key() == self.ranges.key():
            frag_ids = owned[np.asarray(bits)[owned]]
            lay = ctable.layout
            tail_bucket = None
            if lay.tail:
                gfrag = np.asarray(
                    self.engine.catalog.bucketize(ctable, self.ranges))
                tail_bucket = gfrag[ctable.num_rows - lay.tail:]
            inst = ctable.take_fragments(frag_ids, tail_bucket=tail_bucket)
        else:
            bucket = np.asarray(self.engine.catalog.bucketize(ctable, ranges))
            mask = np.asarray(bits)[bucket] & (self._row_shard == sid)
            inst = ctable.select(jnp.asarray(mask))
        if q.join is not None:
            flat, _ = self.engine.catalog.join(
                inst, self.db[q.join.right], q.join.left_key, q.join.right_key)
        else:
            flat = inst
        return flat

    def _degraded_partial(
        self, sid: int, q: Query, reg: _Registered, bits: np.ndarray
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        """Coordinator-side substitute for ``FragmentShard.partial``."""
        flat = self._degraded_flat(sid, q, reg, bits)
        enc, _, sums, counts = inner_group_partials(q, flat, self.engine.catalog)
        return enc.group_values, np.asarray(sums), np.asarray(counts)

    def _shard_arrays(
        self, sid: int, key: int, reg: _Registered, bits: np.ndarray, q: Query
    ):
        """One shard's inner-block arrays for the stacked layout (live path:
        zero-copy on loopback, one RPC on the subprocess backend)."""
        return self.shards[sid].block_arrays(key, reg.ranges, bits, q)

    def _stacked_token(self, degraded: Set[int], bits: np.ndarray) -> Tuple:
        """Freshness token for the stacked arrays.  Degraded shards' slices
        come from the coordinator's authoritative table, so their entry pins
        the *coordinator* table version (a dead shard's table may not even
        exist); live entries pin the shard-local (uid, version) — monotone
        under append/delete and surviving collapse(), whereas a recycled
        object address could alias a stale stack onto fresh data."""
        ctable = self.db[self.table_name]
        per = tuple(
            ("coord", ctable.uid, ctable.version) if sid in degraded
            # A state-less shard outside the degraded set owns no fragments
            # (re-placed away) — it contributes no slice, any sentinel works.
            else (s.state_token() or ("lost",))
            for sid, s in enumerate(self.shards))
        return (per, bits.tobytes())

    def _stacked_for(
        self, key: int, reg: _Registered, bits: np.ndarray,
        degraded: Set[int],
    ) -> StackedInstances:
        """Build (or fetch) the stacked shard-major arrays for one entry.

        The cache key pins the registration + fragment plan; the token guards
        freshness (per-shard table identities + the sketch bits + the
        degraded set), so any shard-side delta application or maintained-bit
        flip rebuilds the stack while the steady state costs one dictionary
        probe.  Degraded shards' slices are built coordinator-side
        (``_degraded_flat``) — the fused launch itself is indifferent to
        where a slice came from.
        """
        catalog = self.engine.catalog
        ckey = ("stacked", key, self.db[self.table_name].uid, id(self.plan))
        token = self._stacked_token(degraded, bits)
        hit = catalog.get_stacked(ckey, token)
        if hit is not None:
            return hit
        q = reg.entry.query
        ranges = reg.ranges
        routable = ranges.key() == self.ranges.key()
        attrs = tuple(q.groupby)

        # The stacked shard axis covers *contacted* shards only: a fragment-
        # skipped shard owns no sketch fragments, so its instance is empty by
        # construction and stacking it would only inflate the padded compute
        # (routing — which shards to skip — stays a host decision; the launch
        # then computes exactly the routed work).
        per_shard: List[Tuple] = []
        contacted_ids: List[int] = []
        for sid in range(self.n_shards):
            owned = self.plan.fragments_of(sid)
            if owned.size == 0 or (routable and not bits[owned].any()):
                continue  # fragment-skip: contributes no stacked slice
            contacted_ids.append(sid)
            if sid in degraded:
                per_shard.append(inner_block_arrays(
                    q, self._degraded_flat(sid, q, reg, bits),
                    self.engine.catalog))
                continue
            try:
                per_shard.append(self._shard_call(
                    sid, "instance",
                    functools.partial(self._shard_arrays, sid, key, reg,
                                      bits, q)))
            except ShardUnavailableError:
                # Mid-build failure: fall through to the degraded slice —
                # the caller's route report picks the shard up via the
                # (mutated) degraded set.
                degraded.add(sid)
                per_shard.append(inner_block_arrays(
                    q, self._degraded_flat(sid, q, reg, bits),
                    self.engine.catalog))

        # Coordinator-owned global group dictionary: np.unique over the
        # concatenated per-shard group key values — the same construction
        # the host-loop merge re-keys with, so numbering (and hence result
        # row order) is identical across the fused, host-loop and
        # single-node paths.
        if not attrs:
            n_groups, group_values = 1, {}
            global_of_local: List[Optional[np.ndarray]] = [None] * len(per_shard)
        else:
            mats, owners = [], []
            for i, a in enumerate(per_shard):
                if a[0].n_groups > 0:
                    mats.append(np.stack(
                        [np.asarray(a[0].group_values[at]) for at in attrs],
                        axis=1))
                    owners.append(i)
            global_of_local = [None] * len(per_shard)
            if mats:
                all_keys = np.concatenate(mats)
                uniq, inv = np.unique(all_keys, axis=0, return_inverse=True)
                n_groups = int(uniq.shape[0])
                group_values = {a: uniq[:, i] for i, a in enumerate(attrs)}
                off = 0
                for i, m in zip(owners, mats):
                    global_of_local[i] = inv[off:off + m.shape[0]]
                    off += m.shape[0]
            else:
                n_groups, group_values = 0, {}

        r_max = max((int(a[1].shape[0]) for a in per_shard), default=0)
        r_pad = _next_pow2(max(r_max, 1))
        s_pad = _next_pow2(max(len(per_shard), 1))
        if self._mesh is not None:
            # Every mesh device takes an equal run of shard slices (weight-0
            # filler on the spare ones), so the launch always spans the mesh.
            s_pad += -s_pad % self._mesh.devices.size
        g_pad = _next_pow2(max(n_groups, 1))
        vals_np = np.zeros((s_pad, r_pad), np.float32)
        gid_np = np.zeros((s_pad, r_pad), np.int32)
        w_np = np.zeros((s_pad, r_pad), np.float32)
        for i, a in enumerate(per_shard):
            enc, where_mask, vals = a
            n = int(where_mask.shape[0])
            if n == 0:
                continue
            gmap = global_of_local[i]
            gid_np[i, :n] = (enc.gid if gmap is None
                             else gmap[enc.gid]).astype(np.int32)
            vals_np[i, :n] = np.asarray(vals, dtype=np.float32)
            w_np[i, :n] = np.asarray(where_mask, dtype=np.float32)

        # A shard may have failed mid-build (degraded grew): re-derive the
        # token so the cached stack is keyed on how it was *actually* built.
        token = self._stacked_token(degraded, bits)
        st = StackedInstances(
            vals=place_stacked(jnp.asarray(vals_np[None]), self._mesh),
            gid=place_stacked(jnp.asarray(gid_np[None]), self._mesh),
            weights=place_stacked(jnp.asarray(w_np[None]), self._mesh),
            n_groups=n_groups,
            g_pad=g_pad,
            group_values=group_values,
            contacted_ids=tuple(contacted_ids),
            token=token,
        )
        catalog.put_stacked(ckey, token, st)
        return st

    @hot_path
    def _launch(self, vals, gid, weights, g_pad: int):
        """The one fused launch: shard_map over the serving mesh when there
        is one (the stacked shard axis is padded to a multiple of its device
        count), the single-device program otherwise."""
        LAUNCH_COUNTS["fused_partials"] += 1
        self._launch_devices = len(vals.sharding.device_set)
        if self._mesh is None:
            return _fused_jit(vals, gid, weights, g_pad)
        LAUNCH_COUNTS["fused_spmd"] += 1
        return _fused_spmd_fn(self._mesh)(vals, gid, weights, g_pad)

    def _result_from_merged(
        self, q: Query, st: StackedInstances,
        sums: np.ndarray, counts: np.ndarray,
    ) -> QueryResult:
        """Finish one query from the fused launch's merged per-group state —
        the same group-level tail as ``_merge_partials``, minus the re-key
        (the stacked layout already speaks the global dictionary)."""
        if not q.groupby:
            s, c = float(sums[0]), float(counts[0])
            agg = _finalize(q.agg.fn, np.array([s], dtype=np.float64),
                            np.array([c], dtype=np.float64))
            return result_from_group_state(q, {}, agg, np.array([c > 0]))
        if st.n_groups == 0:
            return QueryResult(
                group_values={a: np.empty(0) for a in
                              (q.outer_groupby if q.outer_groupby
                               else q.groupby)},
                values=np.empty(0))
        sums64 = sums[:st.n_groups].astype(np.float64)
        counts64 = counts[:st.n_groups].astype(np.float64)
        agg = _finalize(q.agg.fn, sums64, counts64)
        return result_from_group_state(q, st.group_values, agg, counts64 > 0)

    def _run_routed(
        self, q: Query, entry: IndexEntry, t0: float
    ) -> Optional[Tuple[QueryResult, RunInfo]]:
        key = entry.reg_id
        reg = self._registered.get(key)
        if reg is None:
            return None
        self._route_retries = 0
        applied, down = self._catch_up_all()
        degraded = self._degraded_set(down)
        bits = self._resolve_bits(key, reg, degraded)
        if bits is None:
            return None

        if self.fused:
            st = self._stacked_for(key, reg, bits, degraded)
            tl = time.perf_counter()
            sums, counts = self._launch(st.vals, st.gid, st.weights, st.g_pad)
            sums_np, counts_np = np.asarray(sums[0]), np.asarray(counts[0])
            tm = time.perf_counter()
            res = self._result_from_merged(q, st, sums_np, counts_np)
            t1 = time.perf_counter()
            contacted = st.contacted
            per_shard_s: Dict[int, float] = {}
            t_launch, t_merge = tm - tl, t1 - tm
        else:
            ranges = reg.ranges
            routable = ranges.key() == self.ranges.key()
            per_shard_s = {}
            partials = []
            for sid in range(self.n_shards):
                owned = self.plan.fragments_of(sid)
                if owned.size == 0 or (routable and not bits[owned].any()):
                    continue  # fragment-skip the whole shard
                ts = time.perf_counter()
                if sid in degraded:
                    partials.append(self._degraded_partial(sid, q, reg, bits))
                else:
                    try:
                        partials.append(self._shard_call(
                            sid, "partial",
                            functools.partial(self.shards[sid].partial, q, key,
                                              ranges, bits)))
                    except ShardUnavailableError:
                        degraded.add(sid)
                        partials.append(
                            self._degraded_partial(sid, q, reg, bits))
                per_shard_s[sid] = time.perf_counter() - ts
            tm = time.perf_counter()
            res = _merge_partials(q, partials)
            t1 = time.perf_counter()
            contacted = len(per_shard_s)
            t_launch, t_merge = sum(per_shard_s.values()), t1 - tm
        self.last_route = RouteInfo(
            contacted=contacted,
            skipped=self.n_shards - contacted,
            watermark=self.version,
            deltas_applied=applied,
            per_shard_s=per_shard_s,
            t_merge_s=t_merge,
            t_launch_s=t_launch,
            fused=self.fused,
            launch_devices=self._launch_devices if self.fused else 0,
            degraded=bool(degraded),
            failed_shards=tuple(sorted(degraded)),
            n_retries=self._route_retries,
            stale_checkpoints=sum(self.stale_checkpoints),
        )
        info = RunInfo(
            reused=True, created=False, attr=reg.ranges.attr,
            strategy=self.engine.strategy, selectivity=entry.sketch.selectivity,
            t_execute=t1 - t0, repaired=applied > 0,
            shards_contacted=contacted,
            shards_skipped=self.n_shards - contacted,
            degraded=bool(degraded),
        )
        return res, info

    # -- batched serving -------------------------------------------------------
    @hot_path
    def run_batch(self, qs: Sequence[Query]) -> List[Tuple[QueryResult, RunInfo]]:
        """Batched sharded serving: one fused launch for ALL index hits, and
        cross-shard batched admission for the misses.

        Semantically equivalent to ``[self.run(q) for q in qs]`` (results,
        index contents, sketch bits and shard maintainer state — pinned by
        ``tests/test_shard_batch.py``).  Hits are grouped by index entry and
        their stacked arrays concatenate on a leading query axis: the B×S
        per-group partial matrices for the whole batch come out of ONE XLA
        launch (counter-asserted), each query finishing with its own
        HAVING-chain tail on the merged state.  Misses run through the same
        ``core/admission`` pipeline single-node ``run_batch`` uses (shared
        samples/AQR/inner-block/capture per signature group), and every
        captured sketch broadcasts to shard registrations in one pass.
        """
        from repro.core.admission import admit_misses

        if self.engine.selection.reuse_aware and self.engine.strategy != "NO-PS":
            # Same stamp reservation as single-node ``run_batch``: wave
            # deferral records misses out of batch order.
            self.engine.workload.begin_batch(len(qs))
        out: List[Optional[Tuple[QueryResult, RunInfo]]] = [None] * len(qs)
        pending: List[Tuple[int, Query]] = list(enumerate(qs))
        with trace.batch():
            recs = [trace.query_record(i) for i in range(len(qs))]
            while pending:
                misses: List[Tuple[int, Query, trace.Record]] = []
                hits: Dict[int, List[Tuple[int, Query, IndexEntry, float]]] = {}
                for i, q in pending:
                    with trace.recording(recs[i]), trace.span("repro.probe") as probe:
                        entry = (self.engine.index.lookup_entry(q)
                                 if self.engine.strategy != "NO-PS" else None)
                    if entry is None:
                        misses.append((i, q, recs[i]))
                    elif entry.reg_id in self._registered:
                        hits.setdefault(entry.reg_id, []).append(
                            (i, q, entry, probe.seconds))
                    else:
                        # Hit without routed registration (rare: the
                        # registration was dropped): single-node serve +
                        # re-register, exactly like ``run``'s fallback.
                        out[i] = self.engine.run(q)
                        self._register_new()
                if hits:
                    self._serve_hits_batch(list(hits.items()), out)
                if not misses:
                    break
                served, pending = admit_misses(self.engine, misses)
                for i, item in served.items():
                    out[i] = item
                self._register_new()
        return out  # type: ignore[return-value]

    def _serve_hits_batch(
        self,
        groups: List[Tuple[int, List[Tuple[int, Query, IndexEntry, float]]]],
        out: List[Optional[Tuple[QueryResult, RunInfo]]],
    ) -> None:
        """Serve one wave's index hits routed — all entries, one launch."""
        self._route_retries = 0
        applied, down = self._catch_up_all()
        degraded = self._degraded_set(down)
        serving: List[Tuple[int, List, StackedInstances]] = []
        loop_stats: List[Tuple[Tuple[int, ...], Dict[int, float], float, int]] = []
        for key, members in groups:
            reg = self._registered.get(key)
            bits = (self._resolve_bits(key, reg, degraded)
                    if reg is not None else None)
            if bits is None:
                # Maintainer lost mid-flight: single-node serve (the entry
                # still answers from the coordinator), re-register after.
                for i, q, _, _ in members:
                    out[i] = self.engine.run(q)
                self._register_new()
                continue
            if not self.fused:
                loop_stats.append(
                    self._serve_key_host_loop(key, reg, bits, members,
                                              applied, degraded, out))
                continue
            serving.append(
                (key, members, self._stacked_for(key, reg, bits, degraded)))
        if loop_stats:
            contacted = set().union(*(set(c) for c, _, _, _ in loop_stats))
            per_shard_s: Dict[int, float] = {}
            for _, ps, _, _ in loop_stats:
                for sid, dt in ps.items():
                    per_shard_s[sid] = per_shard_s.get(sid, 0.0) + dt
            self.last_route = RouteInfo(
                contacted=len(contacted),
                skipped=self.n_shards - len(contacted),
                watermark=self.version, deltas_applied=applied,
                per_shard_s=per_shard_s,
                t_merge_s=sum(m for _, _, m, _ in loop_stats),
                t_launch_s=sum(per_shard_s.values()), fused=False,
                n_queries=sum(n for _, _, _, n in loop_stats),
                degraded=bool(degraded),
                failed_shards=tuple(sorted(degraded)),
                n_retries=self._route_retries,
                stale_checkpoints=sum(self.stale_checkpoints),
            )
        if not serving:
            return

        tl = time.perf_counter()
        if len(serving) == 1:
            st0 = serving[0][2]
            sums, counts = self._launch(st0.vals, st0.gid, st0.weights,
                                        st0.g_pad)
        else:
            vals, gid, weights, g_pad = self._assemble_batch(serving)
            sums, counts = self._launch(vals, gid, weights, g_pad)
        sums_np, counts_np = np.asarray(sums), np.asarray(counts)
        tm = time.perf_counter()

        union_contacted: set = set()
        n_served = 0
        for row, (key, members, st) in enumerate(serving):
            union_contacted.update(st.contacted_ids)
            for i, q, entry, tp in members:
                tq = time.perf_counter()
                res = self._result_from_merged(
                    q, st, sums_np[row], counts_np[row])
                out[i] = (res, RunInfo(
                    reused=True, created=False,
                    attr=self._registered[key].ranges.attr,
                    strategy=self.engine.strategy,
                    selectivity=entry.sketch.selectivity,
                    t_probe=tp, t_execute=time.perf_counter() - tq,
                    repaired=applied > 0,
                    shards_contacted=st.contacted,
                    shards_skipped=self.n_shards - st.contacted,
                    degraded=bool(degraded),
                ))
                n_served += 1
        t1 = time.perf_counter()
        self.last_route = RouteInfo(
            contacted=len(union_contacted),
            skipped=self.n_shards - len(union_contacted),
            watermark=self.version, deltas_applied=applied,
            per_shard_s={}, t_merge_s=t1 - tm, t_launch_s=tm - tl,
            fused=True, n_queries=n_served,
            launch_devices=self._launch_devices,
            degraded=bool(degraded),
            failed_shards=tuple(sorted(degraded)),
            n_retries=self._route_retries,
            stale_checkpoints=sum(self.stale_checkpoints),
        )

    def _assemble_batch(self, serving: List[Tuple[int, List, StackedInstances]]):
        """Concatenate multiple entries' stacked arrays on the query axis.

        Every entry's arrays are padded to the batch's common (pow2)
        shard/row/group classes; dummy query rows (pow2 tail) carry weight 0
        everywhere.  The assembled tensors are cached in the catalog keyed by
        the ordered entry set and token-guarded by every member's freshness
        token, so a steady-state batch pays one dictionary probe instead of
        re-padding/concatenating per serve.
        """
        catalog = self.engine.catalog
        bkey = ("stacked_batch",) + tuple(key for key, _, _ in serving)
        token = tuple(st.token for _, _, st in serving)
        hit = catalog.get_stacked(bkey, token)
        if hit is not None:
            return hit
        s_pad = max(int(st.vals.shape[1]) for _, _, st in serving)
        r_pad = max(st.r_pad for _, _, st in serving)
        g_pad = max(st.g_pad for _, _, st in serving)
        k_pad = _next_pow2(len(serving))

        def stack(field, dtype):
            parts = [jnp.pad(getattr(st, field),
                             ((0, 0), (0, s_pad - int(st.vals.shape[1])),
                              (0, r_pad - st.r_pad)))
                     for _, _, st in serving]
            if k_pad > len(serving):
                # analyze: waive[PAD01]: filler shape varies with the entry count, but assembly runs only on a stacked-cache miss (registration/eviction/failover), never steady-state — the result is cached under the freshness token
                parts.append(jnp.zeros(
                    (k_pad - len(serving), s_pad, r_pad), dtype))
            return jnp.concatenate(parts)

        assembled = (stack("vals", jnp.float32), stack("gid", jnp.int32),
                     stack("weights", jnp.float32), g_pad)
        catalog.put_stacked(bkey, token, assembled)
        return assembled

    def _serve_key_host_loop(
        self, key: int, reg: _Registered, bits: np.ndarray,
        members: List[Tuple[int, Query, IndexEntry, float]],
        applied: int, degraded: Set[int],
        out: List[Optional[Tuple[QueryResult, RunInfo]]],
    ) -> Tuple[Tuple[int, ...], Dict[int, float], float, int]:
        """Host-loop batch fallback: per-shard partials once per entry (they
        are HAVING-independent), merged once, member tails per query.
        Returns ``(contacted shard ids, per-shard seconds, merge seconds,
        queries served)`` for the caller's aggregated ``last_route``."""
        ranges = reg.ranges
        routable = ranges.key() == self.ranges.key()
        per_shard_s: Dict[int, float] = {}
        partials = []
        q0 = reg.entry.query
        for sid in range(self.n_shards):
            owned = self.plan.fragments_of(sid)
            if owned.size == 0 or (routable and not bits[owned].any()):
                continue
            ts = time.perf_counter()
            if sid in degraded:
                partials.append(self._degraded_partial(sid, q0, reg, bits))
            else:
                try:
                    partials.append(self._shard_call(
                        sid, "partial",
                        functools.partial(self.shards[sid].partial, q0, key,
                                          ranges, bits)))
                except ShardUnavailableError:
                    degraded.add(sid)
                    partials.append(self._degraded_partial(sid, q0, reg, bits))
            per_shard_s[sid] = time.perf_counter() - ts
        tm = time.perf_counter()
        # One HAVING-independent merge per entry; each member pays only its
        # own group-level tail (mirroring the fused path's shared launch).
        state = merge_partials_state(tuple(q0.groupby), partials)
        for i, q, entry, tp in members:
            tq = time.perf_counter()
            res = _result_from_state(q, state)
            out[i] = (res, RunInfo(
                reused=True, created=False, attr=ranges.attr,
                strategy=self.engine.strategy,
                selectivity=entry.sketch.selectivity,
                t_probe=tp, t_execute=time.perf_counter() - tq,
                repaired=applied > 0,
                shards_contacted=len(per_shard_s),
                shards_skipped=self.n_shards - len(per_shard_s),
                degraded=bool(degraded),
            ))
        return (tuple(per_shard_s), dict(per_shard_s),
                time.perf_counter() - tm, len(members))


def merge_partials_state(
    attrs: Tuple[str, ...],
    partials: List[Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]],
) -> Optional[Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]]:
    """Re-key per-shard per-group partials into merged group state.

    Partial sums/counts are re-keyed on group *values* (shard-local group
    numbering is arbitrary) and accumulated in float64; returns
    ``(group_values, sums, counts)``, or ``None`` when no shard contributed
    a group.  HAVING-independent, so one merge serves every query behind the
    same index entry.
    """
    if not attrs:
        s = float(sum(p[1].sum() for p in partials))
        c = float(sum(p[2].sum() for p in partials))
        return {}, np.array([s], dtype=np.float64), np.array([c], dtype=np.float64)
    keys, sums, counts = [], [], []
    for gv, s, c in partials:
        if s.shape[0] == 0:
            continue
        keys.append(np.stack([np.asarray(gv[a]) for a in attrs], axis=1))
        sums.append(s.astype(np.float64))
        counts.append(c.astype(np.float64))
    if not keys:
        return None
    all_keys = np.concatenate(keys)
    uniq, inv = np.unique(all_keys, axis=0, return_inverse=True)
    sums_m = np.zeros(uniq.shape[0], dtype=np.float64)
    counts_m = np.zeros(uniq.shape[0], dtype=np.float64)
    np.add.at(sums_m, inv, np.concatenate(sums))
    np.add.at(counts_m, inv, np.concatenate(counts))
    group_values = {a: uniq[:, i] for i, a in enumerate(attrs)}
    return group_values, sums_m, counts_m


def _result_from_state(
    q: Query,
    state: Optional[Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]],
) -> QueryResult:
    """Finish one query from merged group state: under the integral
    exactness envelope the float32 cast in ``_finalize`` reproduces the
    single-node kernel's per-group values bit-for-bit, and the shared
    ``result_from_group_state`` finishes HAVING chains and outer blocks
    identically to single-node execution."""
    if state is None:
        return QueryResult(
            group_values={a: np.empty(0) for a in
                          (q.outer_groupby if q.outer_groupby else q.groupby)},
            values=np.empty(0))
    group_values, sums_m, counts_m = state
    agg = _finalize(q.agg.fn, sums_m, counts_m)
    return result_from_group_state(q, group_values, agg, counts_m > 0)


def _merge_partials(
    q: Query,
    partials: List[Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]],
) -> QueryResult:
    """Merge per-shard per-group partials into one query's final result."""
    return _result_from_state(q, merge_partials_state(tuple(q.groupby), partials))


def _finalize(fn: str, sums: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """float32 finalization mirroring the executor's kernel arithmetic."""
    sums32 = sums.astype(np.float32)
    counts32 = counts.astype(np.float32)
    if fn == "count":
        return counts32
    if fn == "sum":
        return sums32
    if fn == "avg":
        return sums32 / np.maximum(counts32, np.float32(1.0))
    raise ValueError(f"unknown aggregate {fn!r}")
