"""Pallas TPU kernel: sketch application (the data-skipping scan).

``keep[i] = bits[bucket[i]]`` — translating a sketch into a row keep-mask.
TPUs have no fast arbitrary gather, so the lookup is a one-hot select with
rows kept on lanes: for every sublane row of a ``(sub, 128)`` bucket tile
the kernel compares that ``(1, 128)`` row against a sublane iota of range
ids and takes the max over ranges of ``where(onehot, bits, 0)``, where the
bitmap arrives pre-broadcast along lanes as a ``(ranges, 128)`` block pinned
in VMEM across the grid.  The ``(1, 128)`` result is written straight into
its row of the output tile.  On a fragment-major clustered table the sketch
instance is a slice concatenation instead (``ColumnTable.take_fragments``);
this kernel covers the unclustered layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_TILE = 8192
LANE = 128
SUBLANE = 8


def _filter_kernel(bucket_ref, bits_ref, out_ref):
    n_ranges_p = bits_ref.shape[0]
    range_ids = jax.lax.broadcasted_iota(jnp.int32, (n_ranges_p, LANE), 0)
    bits = bits_ref[...]  # (ranges, 128): bits[r] repeated along lanes

    def row(j, carry):
        hit = bucket_ref[pl.ds(j, 1), :] == range_ids
        out_ref[pl.ds(j, 1), :] = jnp.max(
            jnp.where(hit, bits, 0), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, bucket_ref.shape[0], row, 0)


def sketch_filter_pallas(
    bucket: jax.Array,
    bits: jax.Array,
    rows_per_tile: int = ROWS_PER_TILE,
    interpret: bool = False,
) -> jax.Array:
    """keep (bool[n]) from bucket (int32[n]) and bits (bool[n_ranges])."""
    n = bucket.shape[0]
    n_ranges = bits.shape[0]
    rows_per_tile = min(rows_per_tile, max(SUBLANE * LANE, -(-n // LANE) * LANE))
    rows_per_tile += -rows_per_tile % (SUBLANE * LANE)
    n_pad = -n % rows_per_tile
    bucket_p = jnp.pad(bucket.astype(jnp.int32), (0, n_pad))
    n_ranges_p = n_ranges + (-n_ranges % SUBLANE)
    bits_p = jnp.pad(bits.astype(jnp.int32), (0, n_ranges_p - n_ranges))
    n_tiles = (n + n_pad) // rows_per_tile
    sub = rows_per_tile // LANE

    bucket_2d = bucket_p.reshape(n_tiles * sub, LANE)
    bits_2d = jnp.broadcast_to(bits_p[:, None], (n_ranges_p, LANE))

    out = pl.pallas_call(
        _filter_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((sub, LANE), lambda i: (i, 0)),
            pl.BlockSpec((n_ranges_p, LANE), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((sub, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * sub, LANE), jnp.int32),
        interpret=interpret,
        name="sketch_filter",
    )(bucket_2d, bits_2d)
    return out.reshape(-1)[:n] > 0
