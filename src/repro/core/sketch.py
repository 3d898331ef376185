"""Provenance sketches (Sec. 4): capture, instances, application, selectivity.

A sketch for query Q on range partition ``F_{R,a}`` is the bitvector over
ranges whose fragments contain >= 1 provenance row.  Capture reduces to a
segmented OR of the provenance mask by fragment id — the ``fragment_bitmap``
Pallas kernel.  Application is a *scheduling* decision: on a fragment-major
clustered table (``ColumnTable.cluster_by``) the sketch instance is the
concatenation of the surviving contiguous slices; the ``sketch_filter``
kernel is only the unsorted fallback.  Instances are cached per sketch in
the catalog, so an index hit re-executes over an already-materialized D_P.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.catalog import Catalog, default_catalog
from repro.core.queries import (
    Query,
    QueryResult,
    execute,
    execute_and_provenance,
    provenance_mask,
)
from repro.core.ranges import RangeSet
from repro.core.table import PAD_VALID, ColumnTable, Database
from repro.runtime.shapes import row_class
from repro.runtime.trace import span, to_host

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ProvenanceSketch:
    """An accurate sketch: table + attribute + ranges + membership bits.

    ``table_uid`` / ``table_version`` record which version of the relation the
    bits describe; a mismatch against the live table is the engine's signal to
    repair the sketch through ``repro.core.maintenance`` instead of trusting
    (or re-capturing) it.
    """

    table: str
    ranges: RangeSet
    bits: np.ndarray  # bool, shape (n_ranges,)
    size_rows: int  # |R_P| — rows covered by the sketch instance
    total_rows: int  # |R|
    table_uid: int = 0
    table_version: int = 0

    def current_for(self, table: ColumnTable) -> bool:
        return self.table_uid == table.uid and self.table_version == table.version

    @property
    def attr(self) -> str:
        return self.ranges.attr

    @property
    def selectivity(self) -> float:
        return self.size_rows / max(self.total_rows, 1)

    @property
    def n_fragments(self) -> int:
        return int(self.bits.sum())

    def range_conditions(self) -> Tuple[Tuple[float, float], ...]:
        """The disjunction of [lo, hi) conditions a DBMS would be handed."""
        bounds = np.concatenate([[-np.inf], self.ranges.bounds, [np.inf]])
        out = []
        for i in np.nonzero(self.bits)[0]:
            out.append((float(bounds[i]), float(bounds[i + 1])))
        return tuple(out)


def capture_sketch(
    q: Query,
    db: Database,
    ranges: RangeSet,
    prov: Optional[np.ndarray] = None,
    use_kernel: bool = True,
    catalog: Optional[Catalog] = None,
) -> ProvenanceSketch:
    """Build the accurate sketch R(Q, D, F) for ``q`` on partition ``ranges``."""
    catalog = catalog or default_catalog()
    table = db[q.table]
    if prov is None:
        prov = provenance_mask(q, db, catalog=catalog)
    bucket = catalog.bucketize(table, ranges)
    if use_kernel:
        from repro.kernels import ops as kops

        bits = to_host(kops.fragment_bitmap(jnp.asarray(prov), bucket, ranges.n_ranges))  # analyze: waive[SYNC01]: deliberate merge: sketch bits materialize to host once at capture (admission-time)
    else:
        bits = to_host(  # analyze: waive[SYNC01]: deliberate merge: sketch bits materialize to host once at capture (admission-time)
            jax.ops.segment_max(
                jnp.asarray(prov).astype(jnp.int32), bucket, num_segments=ranges.n_ranges
            )
            > 0
        )
    sizes = catalog.fragment_sizes(table, ranges)
    size_rows = int(sizes[bits].sum())
    return ProvenanceSketch(
        table=q.table,
        ranges=ranges,
        bits=bits.astype(bool),
        size_rows=size_rows,
        total_rows=table.num_rows,
        table_uid=table.uid,
        table_version=table.version,
    )


def capture_sketches_batch(
    qs: Sequence[Query],
    db: Database,
    ranges_list: Sequence[RangeSet],
    provs: Sequence[np.ndarray],
    use_kernel: bool = True,
    catalog: Optional[Catalog] = None,
) -> List[ProvenanceSketch]:
    """Multi-sketch fused capture: B provenance masks, one scan per partition.

    Queries are grouped by (table, partition); each group pays ONE cached
    bucketization and ONE ``fragment_bitmap_batch`` launch that reduces all
    of the group's stacked masks against the shared one-hot incidence — the
    admission pipeline's replacement for B sequential ``capture_sketch``
    calls.  The mask batch is pow2-padded so batch sizes quantize to a few
    compiled shapes.  Bits are bit-identical to per-query capture.
    """
    catalog = catalog or default_catalog()
    out: List[Optional[ProvenanceSketch]] = [None] * len(qs)
    groups: Dict[Tuple, List[int]] = {}
    for i, (q, ranges) in enumerate(zip(qs, ranges_list)):
        groups.setdefault((q.table, ranges.key()), []).append(i)
    for (table_name, _), idxs in groups.items():
        table = db[table_name]
        ranges = ranges_list[idxs[0]]
        bucket = catalog.bucketize(table, ranges)
        stacked = np.stack([np.asarray(provs[i], dtype=bool) for i in idxs])
        b = stacked.shape[0]
        b_pad = 1 << (b - 1).bit_length()
        if b_pad != b:
            stacked = np.concatenate(
                [stacked, np.zeros((b_pad - b, stacked.shape[1]), dtype=bool)])
        if use_kernel:
            from repro.kernels import ops as kops

            bits_b = to_host(  # analyze: waive[SYNC01]: deliberate merge: batched capture materializes the whole wave's bits in one transfer
                kops.fragment_bitmap_batch(jnp.asarray(stacked), bucket, ranges.n_ranges))
        else:
            bits_b = to_host(  # analyze: waive[SYNC01]: deliberate merge: batched capture materializes the whole wave's bits in one transfer
                jax.vmap(
                    lambda p: jax.ops.segment_max(
                        p.astype(jnp.int32), bucket, num_segments=ranges.n_ranges)
                )(jnp.asarray(stacked)) > 0
            )
        sizes = catalog.fragment_sizes(table, ranges)
        for j, i in enumerate(idxs):
            bits = bits_b[j].astype(bool)
            out[i] = ProvenanceSketch(
                table=table_name,
                ranges=ranges_list[i],
                bits=bits,
                size_rows=int(sizes[bits].sum()),
                total_rows=table.num_rows,
                table_uid=table.uid,
                table_version=table.version,
            )
    return out  # type: ignore[return-value]


def sketch_keep_mask(
    sketch: ProvenanceSketch,
    table: ColumnTable,
    use_kernel: bool = True,
    catalog: Optional[Catalog] = None,
) -> Array:
    """Row keep-mask: True iff the row's fragment belongs to the sketch."""
    catalog = catalog or default_catalog()
    bucket = catalog.bucketize(table, sketch.ranges)
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.sketch_filter(bucket, jnp.asarray(sketch.bits))
    return jnp.asarray(sketch.bits)[bucket]


def _pad_instance(
    instance: ColumnTable, rows: np.ndarray, catalog: Catalog
) -> Tuple[ColumnTable, np.ndarray]:
    """Pad an instance's row count to its size class (``row_class``) with
    masked (weight-0) tail rows.

    Steady-state reuse executes over catalog-cached instances whose row
    counts drift with every repair (a handful of rows per mutation), and each
    fresh count is a fresh XLA shape — a recompile on the hot path.  Padding
    every instance to its class quantizes the shape space so a repaired
    instance almost always lands in an already-compiled size class.  The
    tail rows duplicate row 0 and carry ``PAD_VALID=False``; the executor
    zero-weights them, so results are bit-identical (adding 0.0 terms to the
    f32 segment sums is exact).  ``rows`` (the base-table row index per
    instance row) is padded alongside for the catalog's subset-derived
    encodings, and the catalog keeps the count of real rows
    (``Catalog.valid_rows``).
    """
    n = instance.num_rows
    if n == 0:
        return instance, rows
    n_pad = row_class(n)
    valid = np.zeros(n_pad, dtype=bool)
    valid[:n] = True
    if n_pad != n:
        idx = np.zeros(n_pad, dtype=np.int64)
        idx[:n] = np.arange(n)
        instance = instance.gather(jnp.asarray(idx))
        rows = rows[idx]
        catalog.stats["instance_padded"] += 1
    instance = instance.with_column(PAD_VALID, jnp.asarray(valid[:instance.num_rows]))
    catalog.note_valid_rows(instance, n)
    return instance, rows


def _build_instance(
    sketch: ProvenanceSketch, table: ColumnTable, catalog: Catalog
) -> Tuple[ColumnTable, np.ndarray]:
    """Materialize the sketch instance R_P of one table (+ its source rows).

    Clustered tables on the sketch's own partition skip fragments by slicing;
    everything else falls back to the per-row keep-mask kernel.  Either way
    the rows are padded to their size class (masked tail) so reuse execution
    over the cached instance hits an already-compiled shape, and the base-row
    map rides along so group encodings / WHERE masks of the instance derive
    from the base table's cached ones by an O(n) gather instead of fresh host
    passes.
    """
    lay = table.layout
    if lay is not None and lay.matches(sketch.ranges):
        catalog.stats["instance_slices"] += 1
        frag_ids = np.nonzero(sketch.bits)[0]
        # Appended rows live in the layout's unsorted tail; hand
        # ``take_fragments`` the catalog's (delta-refreshed) bucket ids so
        # the tail filter stays delta-sized and never re-searchsorts.
        tail_bucket = None
        if lay.tail:
            n = table.num_rows
            tail_bucket = to_host(  # analyze: waive[SYNC01]: the tail's bucket ids, once per instance build
                catalog.bucketize(table, sketch.ranges))[n - lay.tail:]
        inst, rows = table.take_fragments(
            frag_ids, tail_bucket=tail_bucket, return_rows=True)
        return _pad_instance(inst, rows, catalog)
    catalog.stats["instance_mask"] += 1
    mask = sketch_keep_mask(sketch, table, catalog=catalog)
    rows = np.nonzero(to_host(mask))[0]  # analyze: waive[SYNC01]: the kept rows' positions, once per instance build
    return _pad_instance(table.gather(jnp.asarray(rows)), rows, catalog)


def apply_sketch(
    sketch: ProvenanceSketch, db: Database, catalog: Optional[Catalog] = None
) -> Database:
    """D_P: replace the sketched relation with its sketch instance.

    Instances are cached per (sketch, table) in the catalog: repeated
    applications of a reused sketch cost a dictionary lookup.
    """
    catalog = catalog or default_catalog()
    table = db[sketch.table]
    with span("repro.instance"):
        instance = catalog.get_instance(sketch, table)
        if instance is None:
            instance, rows = _build_instance(sketch, table, catalog)
            catalog.put_instance(sketch, table, instance, rows=rows)
        return db.with_table(instance)


def execute_with_sketch(
    q: Query,
    db: Database,
    sketch: Optional[ProvenanceSketch],
    catalog: Optional[Catalog] = None,
) -> QueryResult:
    """Run ``q`` over ``D_P`` (or D when no sketch) — the instrumented query."""
    if sketch is None:
        return execute(q, db, catalog=catalog)
    return execute(q, apply_sketch(sketch, db, catalog=catalog), catalog=catalog)


def capture_and_execute(
    q: Query, db: Database, ranges: RangeSet, catalog: Optional[Catalog] = None
) -> Tuple[QueryResult, ProvenanceSketch]:
    """Fused capture+execute: one inner-block pass feeds both the result and
    the provenance-derived sketch (the seed evaluated the query twice)."""
    catalog = catalog or default_catalog()
    res, prov = execute_and_provenance(q, db, catalog=catalog)
    sketch = capture_sketch(q, db, ranges, prov=prov, catalog=catalog)
    return res, sketch


def is_safe_sketch(q: Query, db: Database, sketch: ProvenanceSketch) -> bool:
    """Def. 4 checked extensionally: Q(D_P) == Q(D).  (Test utility.)"""
    return execute(q, db).canonical() == execute_with_sketch(q, db, sketch).canonical()


def actual_size(q: Query, db: Database, ranges: RangeSet) -> int:
    """size(Q, D, R, a, R) — ground truth for RSE measurements."""
    return capture_sketch(q, db, ranges).size_rows
