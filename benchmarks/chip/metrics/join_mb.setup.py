"""Join layout (``core/catalog.py`` ``Catalog.join``): summed ``join_bytes``
of the set-up captures, in MB (10^6 bytes): the device bytes of the join
layouts and joined sketch instances they materialized.  A capture's scan is
shared by the batch, so what it joined is counted on the batch's record
(``Record.outer``); each record is counted once.  Nothing to read from a
program without the counter."""
from benchmarks.chip import program_spans

LAYER = "join layout (core/catalog.py Catalog.join)"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    recs = program_spans.records(run.setup_infos)
    recs += [getattr(r, "outer", None) for r in recs]
    unique = {id(r): r for r in recs if r is not None and hasattr(r, "join_bytes")}
    if not unique:
        return None
    return sum(r.join_bytes for r in unique.values()) / 1e6
