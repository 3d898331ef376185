"""Entry (``core/engine.py`` ``run_batch``): mean over the window's answered
queries of the time from the start of their ``repro.run_batch`` call to the
start of their own first ``repro.serve``, in milliseconds: the wait behind
the queries served before them in the same call.  Read from
``RunInfo.trace``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "entry (core/engine.py run_batch)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "p95_ms"


def _wait_s(rec):
    serve = next((s for s in rec.spans if s.name == "repro.serve"), None)
    call = program_spans.ancestor(serve, "repro.run_batch")
    return None if call is None else (serve.start_ns - call.start_ns) / 1e9


def read(run):
    waits = [w for w in map(_wait_s, program_spans.window_records(run)) if w is not None]
    return program_spans.mean(waits) * 1e3 if waits else None
