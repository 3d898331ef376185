"""Join layout (``core/catalog.py`` ``Catalog.join``): summed ``repro.join``
time of the set-up captures, in seconds: the full join layouts their scans
built and the joined sketch instances their warm-ups built.  A capture's
scan is shared by the batch, so its join sits on the batch's record
(``Record.outer``); each record is counted once.  Nothing to read from a
program without ``repro.join`` spans."""
from benchmarks.chip import program_spans

LAYER = "join layout (core/catalog.py Catalog.join)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    recs = program_spans.records(run.setup_infos)
    recs += [getattr(r, "outer", None) for r in recs]
    unique = {id(r): r for r in recs if r is not None}
    joins = [s for r in unique.values() for s in r.spans if s.name == "repro.join"]
    return sum(program_spans.dur_s(s) for s in joins) if joins else None
