"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): mean over
the window's answered queries of the time the host spent in
``repro.to_host``, in milliseconds: waiting for the device's result and
copying it back.  Read from ``RunInfo.trace``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "p50_ms"


def read(run):
    recs = program_spans.window_records(run)
    if not recs:
        return None
    return program_spans.mean(program_spans.seconds(r.spans, "repro.to_host")
                              for r in recs) * 1e3
