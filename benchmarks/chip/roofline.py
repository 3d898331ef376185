"""Least time of a kernel call from its shapes, and the chip's peaks.

``peaks.json`` holds each chip's published peaks, keyed by JAX's
``device_kind``; a kind that is not there is an error, not a default.

The segment aggregate (``GROUP BY ... sum/count/avg``) must, whatever
implements it, read per row in scope a 4-byte group code, the 4-byte
aggregated value (not for ``count``) and the 4-byte WHERE column (where the
template has one), and write 8 bytes per group (a float32 sum and count).
It needs one add per row for the sum and one for the count, and one
multiply per row for the WHERE weight.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def aggregate_bytes(rows: int, groups: int, has_value: bool, has_where: bool) -> int:
    return rows * (4 + 4 * has_value + 4 * has_where) + 8 * groups


def aggregate_ops(rows: int, has_where: bool) -> int:
    return rows * (2 + has_where)


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak HBM
    bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
