"""Hit execution or scan (``core/catalog.py``): the engine catalog's misses
(``Catalog.misses()``: group encodings, join layouts, bucketizations, sketch
instances, WHERE masks built afresh) counted inside the window.  Set-up
builds every product the window's templates use, so this reads 0."""
LAYER = "hit execution and scan (core/queries.py, core/sketch.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "p95_ms"


def read(run):
    return run.catalog_misses
