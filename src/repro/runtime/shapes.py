"""Size classes of the segment aggregate's row and group axes.

Every distinct row or group count that reaches a jitted program compiles a
program of its own, so the executor rounds both counts up to a class first:
a sketch instance's rows when it is built (``core/sketch.py``), and a scan's
rows and every query's groups before the kernel (``core/queries.py``).  A
repair that moves a count by a few rows then almost always stays inside a
class that is already compiled.  The classes follow the kernel's own tiling
rather than powers of two, so the kernel runs few rows and groups that the
query did not ask for.
"""
from __future__ import annotations

from repro.kernels.segment_aggregate import LANE, padded_groups

# Above this many rows, row classes step by an eighth of an octave; below,
# where the aggregate is cheap, tables (and the CPU rehearsals, up to about
# 80,000 rows) keep power-of-two shapes.
ROW_LADDER_FROM = 1 << 17
ROW_STEPS = 8  # steps per octave


def next_pow2(n: int) -> int:
    """The least power of two at or above ``n`` (1 for ``n <= 1``)."""
    return 1 << max(0, n - 1).bit_length()


def row_class(n: int) -> int:
    """Rows a segment aggregate over ``n`` rows runs.

    Up to 2^17 rows, the next power of two.  Above, the least ``2^k * (8 +
    j) / 8`` (``j`` = 0..7) at or above ``n``: a multiple of ``2^(k-3)``,
    hence of twice the kernel's 8 x 1,024-row tile, and at most 12.5% above
    ``n``.
    """
    if n <= ROW_LADDER_FROM:
        return next_pow2(n)
    step = (next_pow2(n) >> 1) // ROW_STEPS  # 2^k < n <= 2^(k+1)
    return -(-n // step) * step


def group_class(n: int) -> int:
    """Groups a segment aggregate over ``n`` groups runs: the next power of
    two up to 128, above that the count the kernel pads to anyway
    (``padded_groups``)."""
    return next_pow2(n) if n <= LANE else padded_groups(n)
