"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): summed
``repro.scan`` time of the set-up captures, in seconds: the full-table inner
blocks their results and provenance come from, each shared scan counted
once.  Read from ``RunInfo.trace``; nothing to read without it."""
from benchmarks.chip import program_spans

LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    recs = program_spans.records(run.setup_infos)
    if not recs:
        return None
    scans = {}
    for r in recs:
        for s in list(r.spans) + [s for s, _ in r.shared]:
            if s.name == "repro.scan":
                scans[id(s)] = s
    return sum(program_spans.dur_s(s) for s in scans.values())
