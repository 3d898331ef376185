"""Selection (``core/strategies.py``, ``aqp/*``): summed ``RunInfo.t_select``
of the set-up captures, in seconds.  Nothing to read without captures."""
LAYER = "selection (core/strategies.py, aqp)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return sum(i.t_select for i in run.setup_infos) if run.setup_infos else None
