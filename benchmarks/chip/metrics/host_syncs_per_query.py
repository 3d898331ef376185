"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): mean
number of device-to-host copies (``repro.to_host``) a window query made
itself, from ``RunInfo.trace.host_syncs``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    recs = program_spans.window_records(run)
    return program_spans.mean(r.host_syncs for r in recs) if recs else None
