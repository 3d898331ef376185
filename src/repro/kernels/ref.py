"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def fragment_bitmap_ref(prov: Array, bucket: Array, n_ranges: int) -> Array:
    """bits[r] = OR over rows in fragment r of the provenance mask."""
    hits = jax.ops.segment_max(
        prov.astype(jnp.int32), bucket, num_segments=n_ranges
    )
    return hits > 0


def fragment_bitmap_batch_ref(provs: Array, bucket: Array, n_ranges: int) -> Array:
    """bits[b, r] = OR over rows in fragment r of provenance mask b."""
    return jax.vmap(lambda p: fragment_bitmap_ref(p, bucket, n_ranges))(provs)


def sketch_filter_ref(bucket: Array, bits: Array) -> Array:
    """keep[i] = bits[bucket[i]] — the sketch's disjunction-of-ranges."""
    return bits.astype(bool)[bucket]


def segment_aggregate_ref(
    values: Array, gid: Array, n_groups: int, weights: Optional[Array] = None
) -> Tuple[Array, Array]:
    """(sums, counts) per group with optional row weights (WHERE mask)."""
    w = jnp.ones_like(values, dtype=jnp.float32) if weights is None else weights.astype(jnp.float32)
    v = values.astype(jnp.float32)
    sums = jax.ops.segment_sum(v * w, gid, num_segments=n_groups)
    counts = jax.ops.segment_sum(w, gid, num_segments=n_groups)
    return sums, counts


# Below this group count the batched reference path materializes the one-hot
# membership matrix and reduces with a dense matmul (the same structure the
# Pallas kernel feeds the MXU): XLA CPU lowers it to a multithreaded GEMM,
# ~5x faster than its single-threaded scatter-add.  Above it, the one-hot
# matrix stops paying for itself and the flat offset-scatter wins.
ONEHOT_MAX_GROUPS = 128
# Row-tile budget for the one-hot path: the (rows, groups) one-hot block is
# rematerialized per tile inside a scan (mirroring the Pallas kernel's row
# tiles) so it stays cache-resident instead of spilling a (B, n, G) tensor.
ONEHOT_TILE_ROWS = 16384
# The one-hot dots carry arbitrary float32 values.  At default precision a
# TPU rounds float32 matmul operands to bf16, which is inexact for integers
# above 256; HIGHEST keeps them float32 (the CPU ignores the setting).
_EXACT = jax.lax.Precision.HIGHEST


def _pow2_tiles(n: int, target: int) -> int:
    """Largest power-of-two tile count dividing ``n`` with tiles >= target."""
    c = 1
    while n % (2 * c) == 0 and n // (2 * c) >= target:
        c *= 2
    return c


def segment_aggregate_batch_ref(
    values: Array, gid: Array, n_groups: int, weights: Optional[Array] = None
) -> Tuple[Array, Array]:
    """(sums, counts) per group for B independent segment problems (B, n).

    Small group counts reduce through a row-tiled one-hot matmul (a scan of
    cache-sized GEMM accumulations); larger ones flatten into ONE segment
    reduction with batch-offset group ids rather than a vmapped scatter
    (XLA lowers the flat scatter-add far better on CPU/GPU, and f32 addition
    order per group is unchanged — row-major — so results match the
    unbatched path bit-for-bit).  The matmul path reassociates the f32
    additions; on integral-valued inputs (the engine's cross-path exactness
    envelope) all orderings are exact and bit-identical.
    """
    b, n = values.shape
    w = (jnp.ones_like(values, dtype=jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    v = values.astype(jnp.float32)
    if n_groups <= ONEHOT_MAX_GROUPS and n > 0:
        groups = jnp.arange(n_groups, dtype=jnp.int32)
        vw = jnp.stack([v * w, w], axis=1)  # (B, 2, n)
        tiles = _pow2_tiles(n, max(ONEHOT_TILE_ROWS // max(b, 1), 1))
        if tiles == 1:
            onehot = (gid[..., None] == groups).astype(jnp.float32)
            out = jnp.einsum("bkn,bng->bkg", vw, onehot, precision=_EXACT)
            return out[:, 0], out[:, 1]
        tn = n // tiles
        vw_t = vw.reshape(b, 2, tiles, tn).transpose(2, 0, 1, 3)  # (T, B, 2, tn)
        g_t = gid.reshape(b, tiles, tn).transpose(1, 0, 2)  # (T, B, tn)

        def step(acc, xs):
            vwk, gk = xs
            onehot = (gk[..., None] == groups).astype(jnp.float32)
            return acc + jnp.einsum("bkn,bng->bkg", vwk, onehot,
                                    precision=_EXACT), None

        acc, _ = jax.lax.scan(
            step, jnp.zeros((b, 2, n_groups), jnp.float32), (vw_t, g_t))
        return acc[:, 0], acc[:, 1]
    offset = (jnp.arange(b, dtype=jnp.int32) * n_groups)[:, None]
    flat_gid = (gid.astype(jnp.int32) + offset).reshape(-1)
    sums, counts = segment_aggregate_ref(
        v.reshape(-1), flat_gid, b * n_groups, w.reshape(-1))
    return sums.reshape(b, n_groups), counts.reshape(b, n_groups)


def flash_attention_ref(
    q: Array, k: Array, v: Array, causal: bool = True, window: int = 0
) -> Array:
    """O = softmax(QK^T / sqrt(d)) V with optional causal/sliding-window mask.

    Shapes: q (B, H, S, D), k/v (B, H, T, D). float32 math.
    """
    qf, kf, vf = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(qf.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf) * scale
    s, t = qf.shape[2], kf.shape[2]
    qpos = jnp.arange(s)[:, None] + (t - s)  # align ends (decode-friendly)
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vf).astype(q.dtype)
