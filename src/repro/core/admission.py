"""Batched admission pipeline: the miss path of ``PBDSEngine.run_batch``.

``PBDSEngine.run`` admits exactly one query at a time, so a burst of N cold
queries pays N stratified samples, N AQR estimate passes, N full-table
capture scans and N maintainer builds — even when the queries differ only in
their HAVING thresholds.  Everything on that list is shareable (Sec. 7.1
sampling reuse; Alg. 1's estimates are candidate- and threshold-independent;
provenance for the whole group derives from one inner-block evaluation), so
batched admission restructures the miss path around *signature groups*:

  wave planning   queries whose sketch an earlier batch member would create
                  are deferred a wave and served as plain index hits, exactly
                  as sequential execution would serve them;
  selection       misses are grouped by inner-block signature
                  (table, GROUP BY, aggregate, WHERE, join); each group
                  shares ONE stratified sample and ONE AQR estimate pass,
                  each member applies its own HAVING at group-level cost, and
                  the fragment-incidence math for every (query, candidate)
                  pair in the whole wave runs as ONE padded vmapped launch
                  (``estimate_size_multi``);
  execution       each signature group evaluates the shared inner block ONCE;
                  every member's result and provenance mask are group-level
                  tails of it (bit-exact — the same code sequential execution
                  runs per query);
  capture         admitted sketches grouped by (table, partition) capture
                  from stacked provenance masks in ONE batched bitmap kernel
                  launch (``capture_sketches_batch``), and maintainers clone
                  their threshold-independent counting state from one build
                  per (signature, partition).

Bit-for-bit parity with sequential ``run`` is a design invariant (the
differential suite in ``tests/test_admission.py`` pins results, index
contents and sketch bits): selection randomness is content-derived
(``PBDSEngine._select_key``), estimate ranking compares exact integral f32
sums, and every shared product is the same object sequential execution would
have pulled from the caches.  The one carve-out is documented on
``PBDSEngine.run_batch``: under ``cluster_tables=True`` the mid-batch
re-cluster invalidates samples, so sample-position-dependent candidate
incidence (non-group-by candidates) may select differently than a
sequential replay that re-sampled the permuted rows.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.aqp.size_estimation import (
    EstimationSpec,
    estimate_size_multi,
    satisfied_groups,
)
from repro.core.index import subsumes
from repro.core.queries import (
    Query,
    QueryResult,
    execute,
    inner_block,
    provenance_from_inner,
    result_from_group_state,
)
from repro.core.safety import stats_prefilter
from repro.core.sketch import apply_sketch, capture_sketches_batch
from repro.core.strategies import (
    RANDOM_STRATEGIES,
    SelectionResult,
    candidate_pool,
    select_attribute,
    selection_cache_key,
)
from repro.runtime import trace
from repro.runtime.guards import hot_path

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.engine import PBDSEngine, RunInfo

Miss = Tuple[int, Query, trace.Record]  # (batch position, query, its record)


def exec_group_key(q: Query) -> Tuple:
    """Inner-block signature: queries with equal keys share FROM/WHERE/GROUP
    BY/aggregate products (sample, AQR estimates, inner-block evaluation,
    maintainer counting state) — only their HAVING chains differ."""
    return q.inner_signature()


def plan_wave(misses: List[Miss]) -> Tuple[List[Miss], List[Miss]]:
    """Split one wave's misses into (admit now, defer to the next wave).

    A miss is deferred when an earlier miss in the same wave subsumes it: in
    sequential execution the earlier query's sketch would exist by the time
    the later one runs, so the later query must be served as an index hit
    against it — not admitted as a duplicate capture.  Deferred queries
    re-probe after the wave lands; if the subsuming query declined to create
    a sketch they are admitted next wave with identical (content-derived)
    randomness, so the outcome still matches sequential order.
    """
    wave: List[Miss] = []
    deferred: List[Miss] = []
    for m in misses:
        if any(subsumes(w[1], m[1]) for w in wave):
            deferred.append(m)
        else:
            wave.append(m)
    return wave, deferred


@hot_path
def admit_misses(
    engine: "PBDSEngine", misses: List[Miss]
) -> Tuple[Dict[int, Tuple[QueryResult, "RunInfo"]], List[Tuple[int, Query]]]:
    """One admission wave: plan (subsumption deferral), admit, return
    ``(served by batch position, deferred (position, query) pairs)``.

    The shared miss-path step of ``PBDSEngine.run_batch`` and
    ``ShardedEngine.run_batch`` — NO-PS skips planning (it never creates
    sketches, so within-batch deferral is moot).
    """
    wave, deferred = (
        plan_wave(misses) if engine.strategy != "NO-PS" else (misses, []))
    served = admit_wave(engine, wave)
    return served, [(i, q) for i, q, _ in deferred]


def _select_wave(
    engine: "PBDSEngine", wave: List[Miss]
) -> Dict[int, SelectionResult]:
    """Candidate selection for the whole wave.

    Cost-based strategies share per-signature-group samples + AQR passes and
    run every (query, candidate) incidence row through one padded device
    launch; random/oracle strategies fall back to per-query selection with
    their content-derived keys (no shareable math).
    """
    db, strategy = engine.db, engine.strategy
    out: Dict[int, SelectionResult] = {}
    if strategy == "NO-PS":
        return {pos: SelectionResult("NO-PS", None, (), {}) for pos, _, _ in wave}
    if strategy in RANDOM_STRATEGIES or strategy == "OPT":
        for pos, q, _ in wave:
            out[pos] = select_attribute(
                strategy, engine._select_key(q), q, db, engine.n_ranges,
                sample_cache=engine.samples, theta=engine.theta, cfg=engine.cfg,
                ranges_for=lambda a, t=q.table: engine.ranges_for(t, a),
                catalog=engine.catalog, aqr_cache=engine.aqr,
                selection=engine.selection,
                selection_cache=engine.selection_cache,
            )
        return out

    sel_cfg = engine.selection
    specs: List[EstimationSpec] = []
    # Parallel to ``specs``: (selection-cache key or None, member positions).
    spec_assign: List[Tuple[Optional[Tuple], List[int]]] = []
    groups: Dict[Tuple, List[Tuple[int, Query]]] = {}
    for pos, q, _ in wave:
        groups.setdefault(exec_group_key(q), []).append((pos, q))
    for members in groups.values():
        # Bucket members by selection-cache key: members sharing a key share
        # one pool + pre-filter + estimate pass and one memoized result —
        # exactly what a sequential replay does (first member computes, the
        # rest hit the SelectionCache).  With the cache disabled
        # (paper-faithful) every member is its own bucket.
        buckets: Dict[Tuple, List[Tuple[int, Query]]] = {}
        order: List[Tuple] = []
        for pos, q in members:
            bk = (selection_cache_key(strategy, q, db[q.table], engine.theta,
                                      engine.n_ranges)
                  if sel_cfg.cache else ("pos", pos))
            if bk not in buckets:
                buckets[bk] = []
                order.append(bk)
            buckets[bk].append((pos, q))
        pending: List[Tuple[Optional[Tuple], List[Tuple[int, Query]],
                            Tuple[str, ...]]] = []
        for bk in order:
            bmembers = buckets[bk]
            ck = bk if sel_cfg.cache else None
            if ck is not None:
                hit = engine.selection_cache.get(ck)
                if hit is not None:
                    for pos, _ in bmembers:
                        out[pos] = hit
                    continue
            q0 = bmembers[0][1]
            cands = candidate_pool(strategy, q0, db, engine.n_ranges,
                                   catalog=engine.catalog)
            if sel_cfg.stats_prefilter:
                cands = stats_prefilter(
                    q0, db, cands,
                    lambda a, t=q0.table: engine.ranges_for(t, a),
                    catalog=engine.catalog)
            if not cands:
                res = SelectionResult(strategy, None, cands, {})
            elif sel_cfg.skip_single_candidate and len(cands) == 1:
                res = SelectionResult(strategy, cands[0], cands, {},
                                      topk=cands)
            else:
                pending.append((ck, bmembers, cands))
                continue
            if ck is not None:
                engine.selection_cache.put(ck, res)
            for pos, _ in bmembers:
                out[pos] = res
        if not pending:
            continue
        # The sample/AQR key is the first member that actually reaches the
        # sampling code — cache hits, empty pools and single-candidate
        # shortcuts never do, so the first *pending* bucket's lead query is
        # what a sequential replay would sample with.
        q0 = pending[0][1][0][1]
        k_s, k_e = jax.random.split(engine._select_key(q0))
        with trace.span("repro.select.sample"):
            samples = engine.samples.get_or_create(
                k_s, db[q0.table], q0.groupby_on_fact(db), engine.theta)
        with trace.span("repro.select.aqr"):
            est, sampled = engine.aqr.get_or_compute(
                k_e, q0, db, samples, engine.theta, engine.cfg)
        for ck, bmembers, cands in pending:
            bq = bmembers[0][1]
            specs.append(EstimationSpec(
                q=bq, samples=samples,
                ranges_by_attr={a: engine.ranges_for(bq.table, a)
                                for a in cands},
                aqr=(est, satisfied_groups(bq, est, sampled)),
            ))
            spec_assign.append((ck, [pos for pos, _ in bmembers]))
    if specs:
        with trace.span("repro.select.estimate"):
            all_estimates = estimate_size_multi(db, specs, engine.cfg, engine.catalog)
        for spec, (ck, positions), estimates in zip(specs, spec_assign,
                                                    all_estimates):
            # Tuple tie-break (attr name second): must match the sequential
            # path in strategies.select_attribute, or batched admission and
            # replay pick different winners at equal estimates.
            ranking = tuple(sorted(estimates,
                                   key=lambda a: (estimates[a].est_rows, a)))
            res = SelectionResult(
                strategy, ranking[0], tuple(spec.ranges_by_attr), estimates,
                topk=ranking[:1])
            if ck is not None:
                engine.selection_cache.put(ck, res)
            for pos in positions:
                out[pos] = res
    return out


def admit_wave(
    engine: "PBDSEngine", wave: List[Miss]
) -> Dict[int, Tuple[QueryResult, "RunInfo"]]:
    """Run one wave of misses through the shared pipeline; returns per-batch-
    position ``(result, info)`` exactly like ``PBDSEngine.run`` would.

    The wave's shared work is timed on the batch's record (``repro.select``,
    ``repro.scan``, ``repro.capture`` > ``repro.capture.bitmap``); each
    member's record points to it and takes an equal share, and holds its own
    tail (``repro.finish``, ``repro.capture.maintainer``/``.warm``)."""
    from repro.core.engine import RunInfo
    from repro.core.maintenance import SketchMaintainer

    catalog = engine.catalog
    out: Dict[int, Tuple[QueryResult, RunInfo]] = {}
    recs = {pos: rec for pos, _, rec in wave}

    with trace.span("repro.select") as select:
        sels = _select_wave(engine, wave)
    t_select = {pos: recs[pos].share(select, len(wave)) for pos in recs}

    # Worth-it partition — ``PBDSEngine._worth_it``, the same rule as ``run``
    # including the reuse-aware discount.  Misses are logged in wave order
    # with their *reserved* batch-position stamps, so ``reach`` sees exactly
    # the prefix a sequential replay would.  One carve-out: a miss deferred
    # to a later wave is recorded after this wave's decisions, so a wave
    # member at a higher batch position cannot count it — this can only
    # shift a decision at a worth-it boundary under non-default weights
    # (under the default weight, first-miss admission does not depend on the
    # reach magnitude).
    reuse = engine.selection.reuse_aware and engine.strategy != "NO-PS"
    admitted: Dict[int, object] = {}  # pos -> RangeSet of the chosen attr
    for pos, q, _ in wave:
        stamp = (engine.workload.record(q, stamp=engine.workload.batch_stamp(pos))
                 if reuse else None)
        if engine._worth_it(sels[pos], q, stamp):
            admitted[pos] = engine.ranges_for(q.table, sels[pos].attr)

    # Physical re-layout happens before the shared scans, mirroring the
    # sequential order (select -> cluster -> capture).
    for pos, q, _ in wave:
        if pos in admitted:
            engine._maybe_cluster(q.table, admitted[pos])
    db = engine.db  # clustering may have replaced tables

    # One inner-block evaluation per signature group feeds every member's
    # result and, for admitted members, the provenance its sketch captures.
    exec_groups: Dict[Tuple, List[Tuple[int, Query]]] = {}
    for pos, q, _ in wave:
        exec_groups.setdefault(exec_group_key(q), []).append((pos, q))
    results: Dict[int, QueryResult] = {}
    provs: Dict[int, np.ndarray] = {}
    t_exec: Dict[int, float] = {}
    for members in exec_groups.values():
        with trace.span("repro.scan") as scan:
            ib = inner_block(db, members[0][1], catalog)
        n_fact = db[members[0][1].table].num_rows
        for pos, q in members:
            with trace.recording(recs[pos]), trace.span("repro.finish") as finish:
                results[pos] = result_from_group_state(
                    q, ib.group_values, ib.agg_np, ib.present)
                if pos in admitted:
                    provs[pos] = provenance_from_inner(q, ib, n_fact)
            t_exec[pos] = recs[pos].share(scan, len(members)) + finish.seconds

    # Fused capture: one bucketize + one batched bitmap launch per partition.
    adm_pos = [pos for pos, _, _ in wave if pos in admitted]
    t_capture: Dict[int, float] = {pos: 0.0 for pos in adm_pos}
    sketches: Dict[int, object] = {}
    if adm_pos:
        q_of = {pos: q for pos, q, _ in wave}
        with trace.span("repro.capture"):
            with trace.span("repro.capture.bitmap") as bitmap:
                sk_list = capture_sketches_batch(
                    [q_of[pos] for pos in adm_pos], db,
                    [admitted[pos] for pos in adm_pos],
                    [provs[pos] for pos in adm_pos], catalog=catalog)
            sketches = dict(zip(adm_pos, sk_list))

            # Maintainer counting state is HAVING-independent: build once per
            # (signature group, partition), clone for the rest of the group.
            bases: Dict[Tuple, SketchMaintainer] = {}
            for pos in adm_pos:
                q, ranges, sketch = q_of[pos], admitted[pos], sketches[pos]
                with trace.recording(recs[pos]):
                    with trace.span("repro.capture.maintainer") as build:
                        bk = (exec_group_key(q), ranges.key())
                        base = bases.get(bk)
                        if base is None:
                            maintainer = SketchMaintainer(q, db, ranges, catalog)
                            bases[bk] = maintainer
                        else:
                            maintainer = base.clone_for(q, db, catalog)
                        engine.index.insert(q, sketch, maintainer=maintainer)
                    # Warm the reuse path while we are already paying capture
                    # cost (instance materialization + compiled shapes), same
                    # as ``run``.
                    with trace.span("repro.capture.warm") as warm:
                        execute(q, apply_sketch(sketch, db, catalog=catalog),
                                catalog=catalog)
                t_capture[pos] = (recs[pos].share(bitmap, len(adm_pos))
                                  + build.seconds + warm.seconds)

    for pos, q, _ in wave:
        sel = sels[pos]
        probe = recs[pos].last("repro.probe")
        if pos in sketches:
            sketch = sketches[pos]
            info = RunInfo(
                reused=False, created=True, attr=sel.attr,
                strategy=engine.strategy, selectivity=sketch.selectivity,
                t_probe=probe.seconds, t_select=t_select[pos],
                t_capture=t_capture[pos], t_execute=t_exec[pos], trace=recs[pos],
            )
        else:
            info = RunInfo(
                reused=False, created=False, attr=None,
                strategy=engine.strategy, selectivity=None,
                t_probe=probe.seconds, t_select=t_select[pos],
                t_execute=t_exec[pos], trace=recs[pos],
            )
        out[pos] = (results[pos], info)
    return out
