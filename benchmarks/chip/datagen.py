"""Seeded table generators for the benchmark's configurations (numpy only).

The public dumps are not available offline, so each configuration names a
generator here that matches its source's schema and row count; the values
are synthetic, and the configuration's file lists what was assumed.  The
benchmark owns these generators so that a change to the engine cannot
change its inputs: the crime generator copies the distributions of the
engine's own ``make_crimes``.

Each generator returns ``{table: (columns, primary_key)}`` with numpy
columns in the dtypes the engine stores.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Tables = Dict[str, Tuple[Dict[str, np.ndarray], Tuple[str, ...]]]


def crimes(rng: np.random.Generator, rows: int) -> Tables:
    """Chicago-Crime-like: 9 numeric attributes.  district, zipcode, beat,
    ward and community are correlated; the ``records`` volume correlates with
    district, year and month, the alignment that makes some partition
    attributes much better sketch choices than others."""
    n = rows
    district = rng.integers(1, 26, n)
    zipcode = 60600 + district * 4 + rng.integers(0, 4, n)
    beat = district * 100 + rng.integers(0, 30, n)
    ward = (district * 2 + rng.integers(0, 3, n)) % 50 + 1
    community = (district * 3 + rng.integers(0, 5, n)) % 77 + 1
    year = rng.integers(2010, 2025, n)
    month = rng.integers(1, 13, n)
    pid = rng.integers(1, 10, n)
    district_heat = np.where(district <= 5, 4.0, np.where(district <= 10, 1.5, 0.6))
    year_heat = 1.0 + 1.8 * (2024 - year) / 14.0
    month_heat = 1.0 + 0.4 * np.sin((month - 1) / 12.0 * 2 * np.pi)
    records = np.minimum(np.maximum(1, rng.zipf(1.8, n)), 500)
    records = np.maximum(1, records * district_heat * year_heat * month_heat)
    cols = dict(pid=pid, month=month, year=year, records=records.astype(np.int64),
                district=district, zipcode=zipcode, beat=beat, ward=ward,
                community=community)
    return {"crimes": ({k: v.astype(np.int32) for k, v in cols.items()},
                       ("beat", "year", "month"))}


GENERATORS = {"crimes": crimes}


def generate(config: dict, seed: int, scale: float = 1.0) -> Tables:
    """The configuration's tables from ``seed``.  ``scale`` shrinks every row
    count (the CPU rehearsal only)."""
    gen = config["generator"]
    sizes = {k: max(64, int(v * scale)) for k, v in gen["rows"].items()}
    rng = np.random.default_rng([int(seed), 0])
    return GENERATORS[gen["name"]](rng, **sizes)
