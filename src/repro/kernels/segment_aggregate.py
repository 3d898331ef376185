"""Pallas TPU kernel: segmented aggregation (group-by SUM/COUNT hot loop).

A group id splits as ``gid = hi * L + lo``, so group ``gid`` is row ``hi``,
lane ``lo`` of an ``(H, L)`` table: ``L`` is 128, or the power of two at or
above ``n_groups`` (at least 16) for fewer groups, and ``H = cdiv(n_groups,
L)``.  Rows stay on lanes: each grid step streams an ``(8, chunk)`` tile of
(group id, value, weight), and for every sublane row of it, ``chunk`` rows,
the kernel builds two one-hots from sublane iotas,

* ``A = (iota_H == hi) * part``, ``(k * H_blk, chunk)``: one ``H_blk``-row
  slab for each of the ``k = 4`` parts, the three exact bf16 parts of the
  row's value ``v * w`` and its weight ``w``;
* ``B = (iota_L == lo)``, ``(L, chunk)``,

and contracts their row axis on the MXU, ``A . B^T -> (k * H_blk, L)``, into
a float32 block that stays resident in VMEM while the row tiles of one
(batch, group block) stream past.  Slab ``p`` of the block is part ``p``
summed per group, already laid out by group: XLA adds the parts after the
launch.  Per group block the VPU builds ``k * H_blk + L`` one-hot rows for
every ``chunk`` rows, and the MXU does ``2 * k * H_blk * L`` flops a row;
``chunk`` shrinks as the one-hots grow, so they stay near a fixed size.

Exactness: the MXU gets only bf16 operands that are exact.  ``B`` is 0/1,
and so is the weight, the row's inclusion mask (WHERE), which is therefore
one part.  A float32 ``x = v * w`` is the sum of three bf16 values: its top
8 significant bits ``p1``, the next 8 of ``x - p1`` as ``p2`` and the rest
``p3`` (at most 8 significant bits), each cut by masking the low 16 bits of
the float32 word, so no rounding mode is involved.  Masking truncates the
magnitude, so every part of ``x`` has the sign of ``x`` and ``|p1| + |p2| +
|p3| = |x|``; on an integral ``x`` every part is an integer.  The products
are exact and accumulate in float32 on the MXU and in the VMEM block.  On
integral inputs whose per-group sum of ``|v * w|`` is at most 2^24, every
partial sum of one part, in any order, is an integer of at most that
magnitude, and so is ``S1 + S2``, so XLA's ``S1 + S2 + S3`` and the result
are bit-identical to a float64 reference.  Inputs must be finite: an
included inf or NaN spoils every group of its ``hi`` row.  A row the mask
drops contributes 0 whatever its value.

A 3-D grid (batch x group blocks x row tiles) covers B independent segment
problems in one launch; the single-problem entry point is the B = 1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
# Rows contracted by one MXU dot (one sublane row of a tile): as many as
# keep the one-hot rows built for it near ONEHOT_ELEMS elements.
ONEHOT_ELEMS = 640 * 1024
MAX_CHUNK = 8192
# At most 128 hi rows (16,384 groups) per group block; bf16 tiles as
# (16, 128), so a block's hi rows pad to 16.
MAX_HI_BLOCK = 128
BF16_SUBLANE = 16
_HI_MASK = -(1 << 16)  # keeps sign, exponent and the top 7 mantissa bits


def _top_bf16(x):
    """``x`` cut to its top 8 significant bits: exact in bf16, as float32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & _HI_MASK
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _bf16_parts(x):
    """Three float32 arrays, each exact in bf16, that sum to a finite ``x``
    exactly."""
    p1 = _top_bf16(x)
    p2 = _top_bf16(x - p1)
    return [p1, p2, x - p1 - p2]


def _row_chunk(onehot_rows: int) -> int:
    """Rows a dot contracts: a power of two, 1,024 at 640 one-hot rows."""
    c = 1 << (ONEHOT_ELEMS // onehot_rows).bit_length() - 1
    return max(LANE, min(MAX_CHUNK, c))


def _segagg_kernel(gid_ref, val_ref, w_ref, out_ref, *, hi_block: int, lo_width: int,
                   chunk: int):
    g = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (hi_block, chunk), 0) + g * hi_block
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (lo_width, chunk), 0)
    lo_bits = lo_width.bit_length() - 1

    def step(j, carry):
        gid = gid_ref[pl.ds(j, 1), :]  # (1, chunk)
        w = w_ref[pl.ds(j, 1), :]
        # A dropped row adds 0 even where its value is not finite.
        parts = _bf16_parts(jnp.where(w != 0.0, val_ref[pl.ds(j, 1), :] * w, 0.0)) + [w]
        # Padded rows carry gid = -1: hi = -1 matches no row of A.
        hit = (gid >> lo_bits) == hi_ids  # (hi_block, chunk)
        a = jnp.concatenate(
            [jnp.where(hit, p, 0.0).astype(jnp.bfloat16) for p in parts], axis=0)
        b = ((gid & (lo_width - 1)) == lo_ids).astype(jnp.bfloat16)  # (lo_width, chunk)
        out_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    # Unrolled, the VPU builds the next chunk's one-hots while the MXU
    # contracts this one's.
    jax.lax.fori_loop(0, gid_ref.shape[0], step, 0, unroll=True)


def segment_aggregate_batch_pallas(
    values: jax.Array,
    gid: jax.Array,
    n_groups: int,
    weights: jax.Array | None = None,
    interpret: bool = False,
    name: str = "segment_aggregate_batch",
):
    """Batched segmented aggregation: B independent segment problems, one grid.

    ``values``/``gid``/``weights`` are (B, n), ``weights`` a 0/1 inclusion
    mask of any dtype; returns (sums f32[B, n_groups], counts f32[B,
    n_groups]).  The batch dimension is the slowest grid axis
    so each (batch, group-block) accumulator stays VMEM-resident while its
    row tiles stream — the shard/query axes of the sharded serving engine's
    stacked launch map onto ``B``.
    """
    b, n = values.shape
    w = (jnp.ones_like(values, dtype=jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    k = 4  # three parts of v * w, then w
    # Fewer than 128 groups take a narrower lo one-hot: 16 to 128 lanes.
    lo_width = min(LANE, max(BF16_SUBLANE, 1 << (n_groups - 1).bit_length()))
    hi_rows = -(-n_groups // lo_width)
    hi_block = min(MAX_HI_BLOCK, hi_rows + (-hi_rows % BF16_SUBLANE))
    n_gblocks = -(-hi_rows // hi_block)
    # Short inputs take a short chunk rather than padding to a full tile.
    chunk = min(_row_chunk(k * hi_block + lo_width),
                max(LANE, 1 << (-(-n // SUBLANE) - 1).bit_length()))
    rows_per_tile = SUBLANE * chunk
    n_pad = -n % rows_per_tile
    # Padded rows get gid = -1 and weight 0: they match no group block.
    gid_p = jnp.pad(gid.astype(jnp.int32), ((0, 0), (0, n_pad)), constant_values=-1)
    val_p = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, n_pad)))
    w_p = jnp.pad(w, ((0, 0), (0, n_pad)))
    n_tiles = (n + n_pad) // rows_per_tile

    gid_2d = gid_p.reshape(b * n_tiles * SUBLANE, chunk)
    val_2d = val_p.reshape(b * n_tiles * SUBLANE, chunk)
    w_2d = w_p.reshape(b * n_tiles * SUBLANE, chunk)

    in_spec = pl.BlockSpec((SUBLANE, chunk), lambda i, g, r: (i * n_tiles + r, 0))
    out_spec = pl.BlockSpec((k * hi_block, lo_width),
                            lambda i, g, r: (i * n_gblocks + g, 0))
    out = pl.pallas_call(
        functools.partial(_segagg_kernel, hi_block=hi_block, lo_width=lo_width,
                          chunk=chunk),
        grid=(b, n_gblocks, n_tiles),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b * n_gblocks * k * hi_block, lo_width),
                                       jnp.float32),
        interpret=interpret,
        name=name,
    )(gid_2d, val_2d, w_2d)
    # (B, group block, part, hi, lo) -> part-major, groups in order.
    parts = (out.reshape(b, n_gblocks, k, hi_block * lo_width)
             .transpose(2, 0, 1, 3).reshape(k, b, n_gblocks * hi_block * lo_width))
    sums = parts[0] + parts[1] + parts[2]
    counts = parts[3]
    return sums[:, :n_groups], counts[:, :n_groups]


def segment_aggregate_pallas(
    values: jax.Array,
    gid: jax.Array,
    n_groups: int,
    weights: jax.Array | None = None,
    interpret: bool = False,
):
    """(sums f32[n_groups], counts f32[n_groups]): the B = 1 batched launch."""
    sums, counts = segment_aggregate_batch_pallas(
        values[None], gid[None], n_groups,
        None if weights is None else weights[None],
        interpret=interpret, name="segment_aggregate")
    return sums[0], counts[0]
