"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): mean over
the window's answered queries of the time inside their ``repro.serve`` spans
that is not ``repro.to_host``, in milliseconds: the host's own work on a
query (probe, instance lookup, WHERE mask, group encoding, dispatch, HAVING).
Read from ``RunInfo.trace``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "p50_ms"


def _host_s(rec) -> float:
    serves = [s for s in rec.spans if s.name == "repro.serve"]
    waits = [s for s in rec.spans if s.name == "repro.to_host"
             and any(program_spans.within(s, v) for v in serves)]
    return sum(program_spans.dur_s(s) for s in serves) - sum(program_spans.dur_s(s) for s in waits)


def read(run):
    recs = program_spans.window_records(run)
    return program_spans.mean(_host_s(r) for r in recs) * 1e3 if recs else None
