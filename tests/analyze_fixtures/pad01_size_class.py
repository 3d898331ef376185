"""PAD01 fixture for the size-class helpers: sizes routed through
``row_class`` / ``group_class`` are fine, directly or through a local; a raw
``len(rows)`` beside them is still flagged."""
import jax.numpy as jnp

from repro.runtime.guards import hot_path
from repro.runtime.shapes import group_class, row_class


@hot_path
def serve(rows, n_groups):
    padded = jnp.zeros(row_class(len(rows)))
    g_pad = group_class(n_groups)
    sums = jnp.zeros(g_pad)
    raw = jnp.ones(len(rows))  # raw row count: still one size class per len
    return padded, sums, raw
