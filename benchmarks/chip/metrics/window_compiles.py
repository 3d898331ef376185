"""Compilation (``runtime/guards.py``'s event): XLA backend compilations
counted inside the window.  Set-up warms every shape, so this reads 0."""
LAYER = "compilation (runtime/guards.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "p95_ms"


def read(run):
    return run.compiles
