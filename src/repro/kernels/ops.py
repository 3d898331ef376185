"""jit'd public wrappers for the Pallas kernels.

On TPU every call runs the Pallas kernel natively (``'pallas'``); there is
no silent fallback to the reference there.  Elsewhere the default is the
pure-jnp oracle (``'ref'``), because interpret-mode row loops are slow at
engine sizes.  A caller picks a mode per call with ``backend=``, or for a
whole block with :func:`forced_backend` (the CPU rehearsal of the chip path
runs the engine with ``'interpret'``, which evaluates the same kernel bodies
on the CPU).  The kernels' tests assert the paths agree.
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fragment_bitmap import (
    fragment_bitmap_batch_pallas,
    fragment_bitmap_pallas,
)
from repro.kernels.segment_aggregate import (
    segment_aggregate_batch_pallas,
    segment_aggregate_pallas,
)
from repro.kernels.sketch_filter import sketch_filter_pallas

Array = jax.Array


MODES = ("pallas", "interpret", "ref")

# Dispatches per (kernel, mode), counted when a wrapper runs on the host —
# once per call, or once per trace when an enclosing jit calls it.  Shows
# which implementation a run actually used.
DISPATCHES: collections.Counter = collections.Counter()

_forced: Optional[str] = None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def forced_backend(mode: str) -> Iterator[None]:
    """Run every kernel call in the block that passes no ``backend=`` in
    ``mode``."""
    global _forced
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}")
    prev, _forced = _forced, mode
    try:
        yield
    finally:
        _forced = prev


def _mode(kernel: str, backend: Optional[str]) -> str:
    """'pallas' | 'interpret' | 'ref'."""
    mode = backend or _forced or ("pallas" if _on_tpu() else "ref")
    DISPATCHES[(kernel, mode)] += 1
    return mode


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fragment_bitmap_jit(prov, bucket, n_ranges, mode):
    if mode == "pallas":
        return fragment_bitmap_pallas(bucket, prov, n_ranges)
    if mode == "interpret":
        return fragment_bitmap_pallas(bucket, prov, n_ranges, interpret=True)
    return ref.fragment_bitmap_ref(prov, bucket, n_ranges)


def fragment_bitmap(prov: Array, bucket: Array, n_ranges: int, backend: Optional[str] = None) -> Array:
    return _fragment_bitmap_jit(prov, bucket, n_ranges, _mode("fragment_bitmap", backend))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fragment_bitmap_batch_jit(provs, bucket, n_ranges, mode):
    if mode == "pallas":
        return fragment_bitmap_batch_pallas(bucket, provs, n_ranges)
    if mode == "interpret":
        return fragment_bitmap_batch_pallas(bucket, provs, n_ranges, interpret=True)
    return ref.fragment_bitmap_batch_ref(provs, bucket, n_ranges)


def fragment_bitmap_batch(
    provs: Array, bucket: Array, n_ranges: int, backend: Optional[str] = None
) -> Array:
    """B stacked provenance masks -> B sketch bitvectors, one scan."""
    return _fragment_bitmap_batch_jit(
        provs, bucket, n_ranges, _mode("fragment_bitmap_batch", backend))


@functools.partial(jax.jit, static_argnums=(2,))
def _sketch_filter_jit(bucket, bits, mode):
    if mode == "pallas":
        return sketch_filter_pallas(bucket, bits)
    if mode == "interpret":
        return sketch_filter_pallas(bucket, bits, interpret=True)
    return ref.sketch_filter_ref(bucket, bits)


def sketch_filter(bucket: Array, bits: Array, backend: Optional[str] = None) -> Array:
    return _sketch_filter_jit(bucket, bits, _mode("sketch_filter", backend))


@functools.partial(jax.jit, static_argnums=(2, 4))
def _segment_aggregate_jit(values, gid, n_groups, weights, mode):
    if mode == "pallas":
        return segment_aggregate_pallas(values, gid, n_groups, weights)
    if mode == "interpret":
        return segment_aggregate_pallas(values, gid, n_groups, weights, interpret=True)
    return ref.segment_aggregate_ref(values, gid, n_groups, weights)


def segment_aggregate(
    values: Array,
    gid: Array,
    n_groups: int,
    weights: Optional[Array] = None,
    backend: Optional[str] = None,
) -> Tuple[Array, Array]:
    return _segment_aggregate_jit(
        values, gid, n_groups, weights, _mode("segment_aggregate", backend))


@functools.partial(jax.jit, static_argnums=(2, 4))
def _segment_aggregate_batch_jit(values, gid, n_groups, weights, mode):
    if mode == "pallas":
        return segment_aggregate_batch_pallas(values, gid, n_groups, weights)
    if mode == "interpret":
        return segment_aggregate_batch_pallas(values, gid, n_groups, weights,
                                              interpret=True)
    return ref.segment_aggregate_batch_ref(values, gid, n_groups, weights)


def segment_aggregate_batch(
    values: Array,
    gid: Array,
    n_groups: int,
    weights: Optional[Array] = None,
    backend: Optional[str] = None,
) -> Tuple[Array, Array]:
    """B independent segment problems (B, n) -> (B, n_groups) sums/counts.

    The sharded serving engine flattens its (query, shard) axes into ``B`` so
    every shard's per-group partials come out of one launch.
    """
    return _segment_aggregate_batch_jit(
        values, gid, n_groups, weights, _mode("segment_aggregate_batch", backend))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _flash_attention_jit(q, k, v, causal, window, mode):
    if mode == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, window=window)
    if mode == "interpret":
        return flash_attention_pallas(q, k, v, causal=causal, window=window, bq=64, bk=64, interpret=True)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def flash_attention(
    q: Array, k: Array, v: Array, causal: bool = True, window: int = 0,
    backend: Optional[str] = None,
) -> Array:
    """Dispatches Pallas on TPU, reference math elsewhere (used by models)."""
    return _flash_attention_jit(q, k, v, causal, window, _mode("flash_attention", backend))
