"""Pallas TPU kernel: segmented aggregation (group-by SUM/COUNT hot loop).

Rows stay on lanes throughout.  Each grid step streams a ``(sub, 128)`` tile
of (group id, value, weight); for every sublane row of the tile the kernel
compares that ``(1, 128)`` row of group ids against a sublane iota of the
group block, giving a ``(group_block, 128)`` one-hot, and accumulates
``where(onehot, value * weight, 0)`` / ``where(onehot, weight, 0)`` into
lane-dense ``(group_block, 128)`` output blocks that stay resident in VMEM
while the row tiles of one (batch, group block) stream past.  The 128 lane
partials of each group are summed by XLA after the launch.

All arithmetic is float32 adds on the VPU: no matmul, so no operand is ever
rounded to bf16.  On integral inputs whose per-group sums stay below 2^24
every association order is exact, so the result is bit-identical to a
float64 reference.

A 3-D grid (batch x group blocks x row tiles) covers B independent segment
problems in one launch; the single-problem entry point is the B = 1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_TILE = 8192
GROUP_BLOCK = 512
LANE = 128
SUBLANE = 8


def _segagg_kernel(gid_ref, val_ref, w_ref, sum_ref, cnt_ref, *, group_block: int):
    g = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    groups = (jax.lax.broadcasted_iota(jnp.int32, (group_block, LANE), 0)
              + g * group_block)

    def row(j, carry):
        gid = gid_ref[pl.ds(j, 1), :]  # (1, 128)
        w = w_ref[pl.ds(j, 1), :]
        vw = val_ref[pl.ds(j, 1), :] * w
        hit = gid == groups  # (group_block, 128)
        sum_ref[...] += jnp.where(hit, vw, 0.0)
        cnt_ref[...] += jnp.where(hit, w, 0.0)
        return carry

    jax.lax.fori_loop(0, gid_ref.shape[0], row, 0)


def segment_aggregate_batch_pallas(
    values: jax.Array,
    gid: jax.Array,
    n_groups: int,
    weights: jax.Array | None = None,
    rows_per_tile: int = ROWS_PER_TILE,
    group_block: int = GROUP_BLOCK,
    interpret: bool = False,
    name: str = "segment_aggregate_batch",
):
    """Batched segmented aggregation: B independent segment problems, one grid.

    ``values``/``gid``/``weights`` are (B, n); returns (sums f32[B, n_groups],
    counts f32[B, n_groups]).  The batch dimension is the slowest grid axis
    so each (batch, group-block) accumulator stays VMEM-resident while its
    row tiles stream — the shard/query axes of the sharded serving engine's
    stacked launch map onto ``B``.
    """
    b, n = values.shape
    w = (jnp.ones_like(values, dtype=jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    # Short inputs take a short tile rather than padding to a full one.
    rows_per_tile = min(rows_per_tile, max(SUBLANE * LANE, -(-n // LANE) * LANE))
    rows_per_tile += -rows_per_tile % (SUBLANE * LANE)
    group_block = min(group_block, n_groups + (-n_groups % SUBLANE))
    n_pad = -n % rows_per_tile
    # Padded rows get gid = -1: they match no group block.
    gid_p = jnp.pad(gid.astype(jnp.int32), ((0, 0), (0, n_pad)), constant_values=-1)
    val_p = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, n_pad)))
    w_p = jnp.pad(w, ((0, 0), (0, n_pad)))
    n_tiles = (n + n_pad) // rows_per_tile
    n_gblocks = -(-n_groups // group_block)
    sub = rows_per_tile // LANE

    gid_2d = gid_p.reshape(b * n_tiles * sub, LANE)
    val_2d = val_p.reshape(b * n_tiles * sub, LANE)
    w_2d = w_p.reshape(b * n_tiles * sub, LANE)

    in_spec = pl.BlockSpec((sub, LANE), lambda i, g, r: (i * n_tiles + r, 0))
    out_spec = pl.BlockSpec((group_block, LANE), lambda i, g, r: (i * n_gblocks + g, 0))
    out_shape = jax.ShapeDtypeStruct((b * n_gblocks * group_block, LANE), jnp.float32)
    sums, counts = pl.pallas_call(
        functools.partial(_segagg_kernel, group_block=group_block),
        grid=(b, n_gblocks, n_tiles),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name=name,
    )(gid_2d, val_2d, w_2d)
    sums = sums.reshape(b, n_gblocks * group_block, LANE).sum(axis=-1)
    counts = counts.reshape(b, n_gblocks * group_block, LANE).sum(axis=-1)
    return sums[:, :n_groups], counts[:, :n_groups]


def segment_aggregate_pallas(
    values: jax.Array,
    gid: jax.Array,
    n_groups: int,
    weights: jax.Array | None = None,
    rows_per_tile: int = ROWS_PER_TILE,
    group_block: int = GROUP_BLOCK,
    interpret: bool = False,
):
    """(sums f32[n_groups], counts f32[n_groups]): the B = 1 batched launch."""
    sums, counts = segment_aggregate_batch_pallas(
        values[None], gid[None], n_groups,
        None if weights is None else weights[None],
        rows_per_tile=rows_per_tile, group_block=group_block,
        interpret=interpret, name="segment_aggregate")
    return sums[0], counts[0]
