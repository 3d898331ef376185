"""Index probe (``core/index.py``): mean ``RunInfo.t_probe`` over the
window's answered queries, in microseconds."""
LAYER = "index probe (core/index.py)"
UNIT = "us"
SOURCE = "program_span"
MOVES = "p50_ms"


def read(run):
    t = [i.t_probe for i in run.window_infos if i is not None]
    return sum(t) / len(t) * 1e6 if t else None
