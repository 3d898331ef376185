"""Pallas TPU kernel: fragment-membership bitmap (sketch capture hot loop).

Computes ``bits[b, r] = OR_{rows i in fragment r} prov[b, i]`` — the inner
loop of ``capture_sketch`` for B provenance masks at once.  The TPU
adaptation replaces the row-at-a-time scatter a CPU engine would use with a
one-hot contraction on the MXU, keeping rows on lanes: for every sublane row
of a ``(sub, 128)`` bucket tile the kernel compares that ``(1, 128)`` row
against a sublane iota of range ids, giving a ``(ranges, 128)`` one-hot, and
contracts it over the lane (row) axis with the B masks' matching
``(B, 128)`` rows: ``(B, 128) . (ranges, 128)^T -> (B, ranges)`` hit counts.
The bitmap block stays resident in VMEM across the whole grid.

Both operands are 0/1 and the per-step counts stay far below 256, so the
bf16 MXU operands and float32 accumulation are exact at any precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_TILE = 8192
LANE = 128
SUBLANE = 8
# bf16 operands tile as (16, 128): the mask batch axis pads to this.
BF16_SUBLANE = 16


def _bitmap_kernel(bucket_ref, provs_ref, out_ref, *, n_ranges_p: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    range_ids = jax.lax.broadcasted_iota(jnp.int32, (n_ranges_p, LANE), 0)

    def row(j, carry):
        onehot = (bucket_ref[pl.ds(j, 1), :] == range_ids).astype(jnp.bfloat16)
        counts = jax.lax.dot_general(
            provs_ref[j], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (B, ranges)
        out_ref[...] = jnp.maximum(out_ref[...], (counts > 0).astype(jnp.int32))
        return carry

    jax.lax.fori_loop(0, bucket_ref.shape[0], row, 0)


def fragment_bitmap_batch_pallas(
    bucket: jax.Array,
    provs: jax.Array,
    n_ranges: int,
    rows_per_tile: int = ROWS_PER_TILE,
    interpret: bool = False,
    name: str = "fragment_bitmap_batch",
) -> jax.Array:
    """bits (bool[B, n_ranges]) from one bucket (int32[n]) and B stacked
    provenance masks (bool[B, n]) — multi-sketch fused capture: one
    bucketization, one scan of the rows, B bitvectors out."""
    b, n = provs.shape
    rows_per_tile = min(rows_per_tile, max(SUBLANE * LANE, -(-n // LANE) * LANE))
    rows_per_tile += -rows_per_tile % (SUBLANE * LANE)
    n_pad = -n % rows_per_tile
    b_pad = -b % BF16_SUBLANE
    # Padding rows point at range 0 with prov = 0: they contribute nothing.
    bucket_p = jnp.pad(bucket.astype(jnp.int32), (0, n_pad))
    provs_p = jnp.pad(provs.astype(jnp.bfloat16), ((0, b_pad), (0, n_pad)))
    n_ranges_p = n_ranges + (-n_ranges % LANE)
    n_tiles = (n + n_pad) // rows_per_tile
    sub = rows_per_tile // LANE

    bucket_2d = bucket_p.reshape(n_tiles * sub, LANE)
    # Row-of-128 major, mask minor: sublane row j of a tile is one (B, 128)
    # slab, read with a leading-axis index inside the kernel.
    provs_3d = provs_p.reshape(b + b_pad, n_tiles * sub, LANE).transpose(1, 0, 2)

    out = pl.pallas_call(
        functools.partial(_bitmap_kernel, n_ranges_p=n_ranges_p),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((sub, LANE), lambda i: (i, 0)),
            pl.BlockSpec((sub, b + b_pad, LANE), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b + b_pad, n_ranges_p), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b + b_pad, n_ranges_p), jnp.int32),
        interpret=interpret,
        name=name,
    )(bucket_2d, provs_3d)
    return out[:b, :n_ranges] > 0


def fragment_bitmap_pallas(
    bucket: jax.Array,
    prov: jax.Array,
    n_ranges: int,
    rows_per_tile: int = ROWS_PER_TILE,
    interpret: bool = False,
) -> jax.Array:
    """bits (bool[n_ranges]) from bucket (int32[n]) and prov (bool[n]): the
    B = 1 batched launch."""
    return fragment_bitmap_batch_pallas(
        bucket, prov[None], n_ranges, rows_per_tile=rows_per_tile,
        interpret=interpret, name="fragment_bitmap")[0]
