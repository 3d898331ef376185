"""Hit execution or scan (``core/queries.py``, ``core/sketch.py``): mean
``RunInfo.t_execute`` over the window's answered queries, in milliseconds.
It ends on host arrays, so it includes the device work and the copy back."""
LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "p50_ms"


def read(run):
    t = [i.t_execute for i in run.window_infos if i is not None]
    return sum(t) / len(t) * 1e3 if t else None
