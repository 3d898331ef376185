"""Seeded tables for the benchmark's configurations (numpy only).

The public dumps are not available offline, so each configuration names a
generator, ``generators/<name>.py``, that matches its source's schema and
row count; the values are synthetic, and the configuration's file lists what
was assumed.  The benchmark owns these generators so that a change to the
engine cannot change its inputs.

A generator module exposes ``generate(rng, **rows) -> {table: (columns,
primary_key)}``, numpy columns in the dtypes the engine stores; ``rows`` is
the configuration's ``generator.rows``.  A generator with foreign keys keeps
them valid at any row counts it is given.
"""
from __future__ import annotations

import os
from typing import Dict, Tuple

import numpy as np

from benchmarks.chip import load_module

Tables = Dict[str, Tuple[Dict[str, np.ndarray], Tuple[str, ...]]]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def generate(config: dict, seed: int, scale: float = 1.0, root: str = ROOT) -> Tables:
    """The configuration's tables from ``seed``.  ``scale`` shrinks every row
    count (the CPU rehearsal only)."""
    gen = config["generator"]
    sizes = {k: max(64, int(v * scale)) for k, v in gen["rows"].items()}
    rng = np.random.default_rng([int(seed), 0])
    return load_module(root, "generators", gen["name"]).generate(rng, **sizes)
