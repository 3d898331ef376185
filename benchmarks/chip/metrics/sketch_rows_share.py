"""Selection (``core/strategies.py``, ``aqp/*``): mean ``RunInfo.selectivity``
of the window's index hits, the rows a hit scans over the table's rows.
Nothing to read in a window without hits."""
LAYER = "selection (core/strategies.py, aqp)"
UNIT = "share"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    s = [i.selectivity for i in run.window_infos if i is not None and i.reused]
    return sum(s) / len(s) if s else None
