"""Kernels (``kernels/ops.py``): the segment aggregate's share of its
roofline, in percent.

Least time over kernel time.  Kernel time is the device time of the events
``kernels.json`` maps to ``segment_aggregate``.  Least time is the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth
(``roofline.py``), summed over the window's ``run_batch`` calls; a call
must read each distinct template's rows in scope once: the sketch instance's
rows on a hit, the table's rows on a scan.  Nothing to read without kernel
events (no trace, or no device).
"""
from benchmarks.chip import roofline

LAYER = "kernels (kernels/ops.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "p50_ms"


def read(run):
    if run.trace is None or "segment_aggregate" not in run.trace.kernel_s:
        return None
    peak = roofline.peaks(run.device_kind)
    least = 0.0
    for batch in run.batches:
        scope = {}
        for i in batch:
            info = run.window_infos[i]
            if info is None:
                continue
            j = run.stream.queries[i][0]
            tp = run.stream.templates[j]
            rows = run.live_rows[tp.table]
            if info.reused:
                rows = int(round(info.selectivity * rows))
            scope[j] = rows
        for j, rows in scope.items():
            tp = run.stream.templates[j]
            nbytes = roofline.aggregate_bytes(rows, run.groups[j],
                                              tp.agg_fn != "count", False)
            least += roofline.least_time_s(roofline.aggregate_ops(rows, False),
                                           nbytes, peak)
    return least / run.trace.kernel_s["segment_aggregate"] * 100.0
