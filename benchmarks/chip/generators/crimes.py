"""Chicago-Crime-like table: 9 numeric attributes, one table.

district, zipcode, beat, ward and community are correlated; the ``records``
volume correlates with district, year and month, the alignment that makes
some partition attributes much better sketch choices than others.  The
distributions copy the engine's own ``make_crimes``.
"""
import numpy as np


def generate(rng: np.random.Generator, rows: int) -> dict:
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
