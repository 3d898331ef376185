"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): mean
bytes a window query copied from the device to the host, in MB (10^6 bytes),
from ``RunInfo.trace.d2h_bytes``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    recs = program_spans.window_records(run)
    return program_spans.mean(r.d2h_bytes for r in recs) / 1e6 if recs else None
