"""Capture (``core/sketch.py``, ``kernels/fragment_bitmap.py``): summed
``RunInfo.t_capture`` of the set-up captures, in seconds.  Nothing to read
without captures."""
LAYER = "capture (core/sketch.py, kernels/fragment_bitmap.py)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return sum(i.t_capture for i in run.setup_infos) if run.setup_infos else None
