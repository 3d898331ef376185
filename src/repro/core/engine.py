"""PBDSEngine — the Fig. 3 workflow as a single online component.

For each incoming query:
  1. probe the sketch index; on a hit, run the instrumented query over the
     catalog-cached sketch instance (fragment skipping, no per-row scan);
  2. otherwise run the configured candidate-selection strategy (sampling is
     cached/reused per Sec. 7.1, AQR estimate passes are cached
     threshold-independently per table version), capture an accurate sketch
     on the chosen attribute via the fused capture+execute path, store it,
     and return the shared result;
  3. when no viable candidate exists, fall back to NO-PS execution.

``run_batch`` accepts a batch of concurrent queries and routes the misses
through the batched admission pipeline (``repro.core.admission``): grouped
shared-sample/shared-AQR selection in one padded device launch, one
inner-block scan per signature group, and multi-sketch fused capture —
bit-identical to sequential ``run`` but with the per-miss cost shared.

All repeated host work (group-by dictionary encoding, join materialization,
bucketization, distinct counts, sketch instances) lives in the engine's
``Catalog``.  With ``cluster_tables=True`` the first created sketch per table
also re-clusters that table fragment-major (``ColumnTable.cluster_by``) so
instance materialization is a slice concatenation; it is opt-in because the
physical reorder reassociates float32 aggregation for queries grouping on
other attributes (bit-identical results are the default contract).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.aqp.sampling import AQRCache, SampleCache
from repro.aqp.size_estimation import EstimationConfig
from repro.core.catalog import Catalog
from repro.core.index import IndexEntry, SketchIndex
from repro.core.maintenance import build_maintainer, repair_sketch
from repro.core.queries import (
    Query,
    QueryResult,
    execute,
    inner_block,
    provenance_from_inner,
    result_from_inner,
)
from repro.core.ranges import RangeSet, equi_depth_ranges
from repro.core.sketch import ProvenanceSketch, apply_sketch, capture_sketch, execute_with_sketch
from repro.core.strategies import (
    SelectionCache,
    SelectionConfig,
    SelectionResult,
    select_attribute,
)
from repro.core.table import Database
from repro.core.workload import WorkloadLog
from repro.runtime import trace
from repro.runtime.guards import hot_path
from repro.runtime.stable_hash import stable_hash32


@dataclasses.dataclass
class RunInfo:
    reused: bool
    created: bool
    attr: Optional[str]
    strategy: str
    selectivity: Optional[float]
    t_select: float = 0.0
    t_capture: float = 0.0
    t_execute: float = 0.0
    # Reused-path timing split: ``t_probe`` is the index lookup, ``t_repair``
    # the bring-current work on a mutated table (both used to hide inside
    # ``t_execute``, silently inflating the reuse numbers).
    t_probe: float = 0.0
    t_repair: float = 0.0
    # Index hit on a mutated table: the sketch was brought current before use
    # (incrementally maintained, or re-captured when maintenance refused —
    # catalog.stats['sketch_maintained'/'sketch_recaptured'] tell them apart).
    repaired: bool = False
    # Fragment-sharded serving (``repro.core.shard``): how many shards were
    # sent work vs skipped because the sketch touched none of their
    # fragments.  ``None`` for single-node execution.
    shards_contacted: Optional[int] = None
    shards_skipped: Optional[int] = None
    # Sharded serving answered with one or more shards down/lagging: their
    # fragment slices were served from the coordinator's authoritative table
    # (bit-identical, but without that shard's parallelism).  The per-route
    # detail (which shards, how many retries) lives on ``RouteInfo``.
    degraded: bool = False
    # The query's spans and counters (``repro.runtime.trace``); the ``t_*``
    # fields above are read off its spans.
    trace: Optional[trace.Record] = None

    @property
    def t_total(self) -> float:
        return self.t_probe + self.t_select + self.t_capture + self.t_repair + self.t_execute


class PBDSEngine:
    def __init__(
        self,
        db: Database,
        strategy: str = "CB-OPT-GB",
        n_ranges: int = 100,
        theta: float = 0.05,
        cfg: EstimationConfig = EstimationConfig(),
        seed: int = 0,
        min_selectivity_gain: float = 0.9,
        cluster_tables: bool = False,
        max_delta_chain: int = 64,
        compact_tail_frac: Optional[float] = None,
        selection: Optional[SelectionConfig] = None,
    ):
        self.db = db
        self.strategy = strategy
        self.n_ranges = n_ranges
        self.theta = theta
        self.cfg = cfg
        self.index = SketchIndex()
        self.samples = SampleCache()
        self.aqr = AQRCache()
        self.catalog = Catalog()
        # Selection-path knobs (stats pre-filter, single-candidate shortcut,
        # reuse-aware worth-it, whole-pass memoization) — all ON by default;
        # pass ``SelectionConfig.paper_faithful()`` for seed/Sec. 8-9 behavior.
        self.selection = SelectionConfig() if selection is None else selection
        self.selection_cache = SelectionCache()
        self.workload = WorkloadLog(self.selection.reuse_window)
        self.cluster_tables = cluster_tables
        self._base_key = jax.random.PRNGKey(seed)
        self._ranges_cache: Dict[Tuple[str, str], RangeSet] = {}
        # Delta chains pin every prior version's columns; past this depth the
        # engine advances all maintainers and collapses the history.
        self.max_delta_chain = max_delta_chain
        # Sketches estimated to cover >= this fraction of the table are not
        # worth creating (problem definition (i) in Sec. 4.5).
        self.min_selectivity_gain = min_selectivity_gain
        # When set, a clustered table whose unsorted append tail exceeds this
        # fraction of its rows is physically compacted (tail folded back into
        # fragment-major order) so sketch application returns to pure slice
        # concatenation.  Off by default: compaction is a full-table permute
        # and drops row-position caches, the same trade as cluster_by.
        self.compact_tail_frac = compact_tail_frac

    def selection_state(self) -> dict:
        """Picklable snapshot of the reuse-aware selection state: the
        ``WorkloadLog`` miss window (the reuse-aware cost model's input)
        plus the ``SelectionCache`` hit/miss counters.  A coordinator
        restart that drops this silently reverts CB-OPT-GB to reuse-blind
        declines — checkpoint it alongside the table state."""
        return {
            "workload": self.workload.snapshot(),
            "selection_cache": {"hits": self.selection_cache.hits,
                                "misses": self.selection_cache.misses},
        }

    def restore_selection_state(self, state: Mapping) -> None:
        """Inverse of ``selection_state`` (cache *stats* restore; cached
        selection results themselves rebuild on first use)."""
        self.workload = WorkloadLog.from_snapshot(state["workload"])
        sc = state.get("selection_cache")
        if sc is not None:
            self.selection_cache.hits = int(sc["hits"])
            self.selection_cache.misses = int(sc["misses"])

    def _select_key(self, q: Query) -> jax.Array:
        """Per-query selection randomness, derived from query *content*.

        A chained key stream would make the engine's choices depend on the
        order misses happen to arrive in; folding the query signature into
        the seed key instead makes sequential ``run`` and batched
        ``run_batch`` admission draw identical randomness for identical
        queries — the invariant the differential admission suite pins.

        The hash must also be identical in every *process*: once shards are
        real processes, a coordinator and replica deriving different keys
        for the same query would draw different selection randomness.
        ``stable_hash32`` is repr-compatible for plain-python signatures
        (same key stream as before) but immune to ``PYTHONHASHSEED``, numpy
        scalar reprs and set iteration order — pinned by the subprocess
        determinism test in ``tests/test_guards.py``.
        """
        h = stable_hash32(q.signature())
        return jax.random.fold_in(self._base_key, h)

    def ranges_for(self, table: str, attr: str) -> RangeSet:
        ck = (table, attr)
        if ck not in self._ranges_cache:
            self._ranges_cache[ck] = equi_depth_ranges(self.db[table], attr, self.n_ranges)
        return self._ranges_cache[ck]

    def _maybe_cluster(self, table_name: str, ranges: RangeSet) -> None:
        """Fragment-major re-layout, once per table (first created sketch).

        Equi-depth bounds are permutation-invariant so the ranges cache stays
        valid, but cached sample *indices* refer to row positions and must be
        dropped.
        """
        if not self.cluster_tables:
            return
        table = self.db[table_name]
        if table.layout is not None:
            return
        self.db = self.db.with_table(table.cluster_by(ranges))
        self.samples.invalidate(table_name)
        self.selection_cache.invalidate(table_name)
        self.catalog.invalidate_table(table)  # old object can never hit again
        self.catalog.stats["cluster"] += 1

    # -- mutations -------------------------------------------------------------
    def append_rows(self, table_name: str, rows: Mapping[str, np.ndarray]) -> None:
        """Append a batch; sketches repair lazily on their next index hit."""
        self.db = self.db.with_table(self.db[table_name].append(rows))
        self.catalog.stats["table_append"] += 1
        self._bound_history(table_name)
        self._maybe_compact(table_name)

    def _maybe_compact(self, table_name: str) -> None:
        """Fold an oversized unsorted tail back into fragment-major order.

        Maintainer state is permutation-invariant so index entries survive,
        but every maintainer must be advanced first: compaction drops the
        delta chain, so a lagging maintainer could no longer catch up.
        """
        table = self.db[table_name]
        lay = table.layout
        if (self.compact_tail_frac is None or lay is None or
                lay.tail <= self.compact_tail_frac * max(table.num_rows, 1)):
            return
        from repro.core.maintenance import MaintenanceError

        for e in self.index.entries():
            if e.query.table != table_name or e.maintainer is None:
                continue
            try:
                e.maintainer.apply(table, self.db)
                e.sketch = e.maintainer.to_sketch(table, self.catalog)
            except MaintenanceError:
                e.maintainer = None
        self.db = self.db.with_table(table.compact())
        self.catalog.invalidate_chain(table)
        self.samples.invalidate(table_name)
        self.selection_cache.invalidate(table_name)
        self.catalog.stats["compact"] += 1

    def delete_rows(self, table_name: str, mask: np.ndarray) -> None:
        """Delete the masked rows; sketches repair lazily on their next hit."""
        self.db = self.db.with_table(self.db[table_name].delete(mask))
        self.catalog.stats["table_delete"] += 1
        self._bound_history(table_name)

    def _bound_history(self, table_name: str) -> None:
        """Cap the delta chain: advance every maintainer to the current
        version (delta-sized work), then drop the parent references so prior
        versions' columns can be freed.  Caches keyed to old versions rebuild
        once on next touch — O(table) once per ``max_delta_chain`` mutations,
        amortized away."""
        table = self.db[table_name]
        if table.delta_depth() <= self.max_delta_chain:
            return
        from repro.core.maintenance import MaintenanceError

        for e in self.index.entries():
            if e.query.table != table_name or e.maintainer is None:
                continue
            try:
                e.maintainer.apply(table, self.db)
                e.sketch = e.maintainer.to_sketch(table, self.catalog)
            except MaintenanceError:
                e.maintainer = None  # next hit re-captures
        self.db = self.db.with_table(table.collapse())
        # Drop every chain version's catalog entries and cached samples so the
        # collapsed chain's columns can actually be freed.
        self.catalog.invalidate_chain(table)
        self.samples.invalidate(table_name)
        self.selection_cache.invalidate(table_name)
        self.catalog.stats["history_collapse"] += 1

    def _current_sketch(self, entry: IndexEntry) -> Tuple[ProvenanceSketch, bool]:
        """The entry's sketch, transparently repaired if the table mutated."""
        table = self.db[entry.query.table]
        if entry.sketch.current_for(table):
            return entry.sketch, False
        result, maintainer = repair_sketch(
            entry.query, self.db, entry.sketch, entry.maintainer, catalog=self.catalog)
        entry.sketch = result.sketch
        entry.maintainer = maintainer
        return result.sketch, True

    @contextlib.contextmanager
    def _serving(self, rec: trace.Record):
        """``rec`` current under a ``repro.serve`` span; the catalog's misses
        and evictions meanwhile are counted into it."""
        stats = self.catalog.stats
        misses, evictions = self.catalog.misses(), stats["evictions"]
        try:
            with trace.recording(rec), trace.span("repro.serve"):
                yield
        finally:
            rec.catalog_misses += self.catalog.misses() - misses
            rec.catalog_evictions += stats["evictions"] - evictions

    @hot_path
    def _serve_hit(self, q: Query, entry: IndexEntry) -> Tuple[QueryResult, ProvenanceSketch, bool]:
        """Serve one index hit over the (repaired-if-stale) sketch instance —
        the shared hit path of ``run`` and ``run_batch``, inside the query's
        ``repro.serve`` span."""
        with trace.span("repro.repair"):
            sketch, repaired = self._current_sketch(entry)
        return execute_with_sketch(q, self.db, sketch, catalog=self.catalog), sketch, repaired

    def _hit_info(self, rec: trace.Record, sketch: ProvenanceSketch,
                  repaired: bool) -> RunInfo:
        """A hit's ``RunInfo``: its execution runs from the end of the repair
        to the end of the serve."""
        serve, repair = rec.last("repro.serve"), rec.last("repro.repair")
        return RunInfo(
            reused=True, created=False, attr=sketch.attr, strategy=self.strategy,
            selectivity=sketch.selectivity, t_probe=rec.last("repro.probe").seconds,
            t_repair=repair.seconds, t_execute=(serve.end_ns - repair.end_ns) / 1e9,
            repaired=repaired, trace=rec,
        )

    def _worth_it(self, sel: SelectionResult, q: Query,
                  stamp: Optional[int]) -> bool:
        """The admission rule (problem definition (i), Sec. 4.5), shared by
        ``run`` and the batched planner.

        Paper rule: create unless the estimate covers >= ``min_selectivity_gain``
        of the table.  Reuse-aware (default): each recent-window query this
        sketch would serve (``WorkloadLog.reach``, self-inclusive) discounts
        the coverage by ``reuse_weight`` first — expected future index hits
        buy back capture cost even for broad sketches."""
        if sel.attr is None:
            return False
        est = sel.estimates.get(sel.attr) if sel.estimates else None
        if est is None:
            return True
        gain = est.est_selectivity
        if stamp is not None:
            gain -= self.selection.reuse_weight * self.workload.reach(q, stamp)
        return gain < self.min_selectivity_gain

    def _scan(self, q: Query, provenance: bool = False):
        """The whole table's inner block (``repro.scan``), then the query's
        own tail (``repro.finish``): ``(result, provenance mask or None,
        seconds)``."""
        with trace.span("repro.scan") as scan:
            ib = inner_block(self.db, q, self.catalog)
        with trace.span("repro.finish") as finish:
            res = result_from_inner(q, ib)
            prov = (provenance_from_inner(q, ib, self.db[q.table].num_rows)
                    if provenance else None)
        return res, prov, scan.seconds + finish.seconds

    @hot_path
    def run(self, q: Query) -> Tuple[QueryResult, RunInfo]:
        rec = trace.query_record(0)
        with trace.recording(rec):
            with self._serving(rec):
                with trace.span("repro.probe") as probe:
                    entry = self.index.lookup_entry(q) if self.strategy != "NO-PS" else None
                if entry is not None:
                    res, sketch, repaired = self._serve_hit(q, entry)
            if entry is not None:
                return res, self._hit_info(rec, sketch, repaired)

            if self.strategy == "NO-PS":
                res, _, t_exec = self._scan(q)
                return res, RunInfo(False, False, None, "NO-PS", None, t_execute=t_exec,
                                    t_probe=probe.seconds, trace=rec)

            stamp = self.workload.record(q) if self.selection.reuse_aware else None
            with trace.span("repro.select") as select:
                sel = select_attribute(
                    self.strategy, self._select_key(q), q, self.db, self.n_ranges,
                    sample_cache=self.samples, theta=self.theta, cfg=self.cfg,
                    ranges_for=lambda a: self.ranges_for(q.table, a),
                    catalog=self.catalog, aqr_cache=self.aqr,
                    selection=self.selection, selection_cache=self.selection_cache,
                )

            if not self._worth_it(sel, q, stamp):
                res, _, t_exec = self._scan(q)
                return res, RunInfo(False, False, None, self.strategy, None,
                                    t_probe=probe.seconds, t_select=select.seconds,
                                    t_execute=t_exec, trace=rec)

            with trace.span("repro.capture") as layout:
                ranges = self.ranges_for(q.table, sel.attr)
                self._maybe_cluster(q.table, ranges)
            # Fused path: one inner-block evaluation yields the result AND the
            # provenance the sketch is captured from (the seed ran it twice).
            res, prov, t_exec = self._scan(q, provenance=True)
            with trace.span("repro.capture") as capture:
                with trace.span("repro.capture.bitmap"):
                    sketch = capture_sketch(q, self.db, ranges, prov=prov, catalog=self.catalog)
                # Maintenance state rides along from capture: the inner-block
                # products it needs (group encoding, join layout, bucketization)
                # are all catalog hits at this point, so the build costs one
                # delta-free counting pass.
                with trace.span("repro.capture.maintainer"):
                    maintainer = build_maintainer(q, self.db, ranges, self.catalog)
                    self.index.insert(q, sketch, maintainer=maintainer)
                # Warm the reuse path now, while we are already paying capture
                # cost: materialize the sketch instance and run the instrumented
                # query once so its catalog entries (instance, group encoding,
                # join layout) and kernel compilations exist before the first
                # index hit.
                with trace.span("repro.capture.warm"):
                    execute(q, apply_sketch(sketch, self.db, catalog=self.catalog),
                            catalog=self.catalog)
            return res, RunInfo(
                reused=False, created=True, attr=sel.attr, strategy=self.strategy,
                selectivity=sketch.selectivity, t_probe=probe.seconds,
                t_select=select.seconds, t_capture=layout.seconds + capture.seconds,
                t_execute=t_exec, trace=rec,
            )

    @hot_path
    def run_batch(self, qs: Sequence[Query]) -> List[Tuple[QueryResult, RunInfo]]:
        """Batched admission: serve index hits immediately, admit the misses
        through the shared-selection / fused-capture pipeline.

        Semantically equivalent to ``[self.run(q) for q in qs]`` — results,
        index contents and sketch bits are pinned bit-identical by
        ``tests/test_admission.py``.  One carve-out: with
        ``cluster_tables=True`` the first admission physically re-clusters
        the table mid-batch and invalidates cached samples; sequential
        execution then re-samples the permuted rows for later same-batch
        misses while the batch shares the pre-cluster sample, so strategies
        whose candidate incidence depends on sample *row positions*
        (non-group-by candidates, e.g. CB-OPT-REL/CB-OPT) may choose
        differently.  Group-by-candidate strategies (CB-OPT-GB, the default
        regime) pin incidence on group values and stay bit-identical either
        way.  The miss-path cost is shared:
        misses are grouped by inner-block signature so each group pays ONE
        stratified sample, ONE AQR estimate pass, and ONE table scan feeding
        every admitted sketch's provenance; all selection math runs as a
        single padded (query x candidate) device launch, and capture emits B
        bitvectors from one bucketization.  Queries whose sketch would be
        created by an earlier query in the same batch are deferred a wave and
        served as ordinary index hits, exactly as sequential execution would.
        """
        from repro.core.admission import admit_misses

        if self.selection.reuse_aware and self.strategy != "NO-PS":
            # Reserve workload-log stamps per batch position up front: wave
            # deferral records misses out of arrival order, and the stamps
            # keep ``reach`` order-exact with a sequential replay.
            self.workload.begin_batch(len(qs))
        out: List[Optional[Tuple[QueryResult, RunInfo]]] = [None] * len(qs)
        pending: List[Tuple[int, Query]] = list(enumerate(qs))
        with trace.batch():
            recs = [trace.query_record(i) for i in range(len(qs))]
            while pending:
                misses: List[Tuple[int, Query, trace.Record]] = []
                for i, q in pending:
                    with self._serving(recs[i]):
                        with trace.span("repro.probe"):
                            entry = (self.index.lookup_entry(q)
                                     if self.strategy != "NO-PS" else None)
                        if entry is not None:
                            res, sketch, repaired = self._serve_hit(q, entry)
                    if entry is None:
                        misses.append((i, q, recs[i]))
                    else:
                        out[i] = res, self._hit_info(recs[i], sketch, repaired)
                if not misses:
                    break
                served, pending = admit_misses(self, misses)
                for i, item in served.items():
                    out[i] = item
        return out  # type: ignore[return-value]
