"""Rows of the shared tests' per-cell tables for cells added after those
tests were written, so that each shared test holds every cell of
``BENCHMARK.json`` to the same checks.

``TRACED`` of ``test_chipbench_rehearsal``: the per-layer metrics a traced
CPU rehearsal of the cell reads (no device plane on the CPU, so no trace
metrics)."""
import test_chipbench_rehearsal

test_chipbench_rehearsal.TRACED.setdefault("tpch-sf1-join-hits", {
    "probe_us", "execute_ms", "sketch_rows_share", "select_s.setup", "capture_s.setup",
    "window_compiles", "window_catalog_misses", "join_s.setup", "join_mb.setup"})
