"""Size classes of the segment aggregate (``repro.runtime.shapes``): the row
ladder and the group class, and the executor's aggregate at classes that are
not powers of two, bit for bit against the reference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.queries import segment_sums_counts
from repro.kernels import ops, ref
from repro.kernels.segment_aggregate import SUBLANE, _group_tiling, padded_groups
from repro.runtime.shapes import ROW_LADDER_FROM, group_class, next_pow2, row_class

ROW_TILE = SUBLANE * 1024  # the kernel's row tile at 1,024-row chunks


def _row_counts():
    rng = np.random.default_rng(0)
    edges = [1 << k for k in range(25)]
    near = [e + d for e in edges for d in (-1, 1) if e + d > 0]
    sizes = [5_767_168, 5_760_000, 6_001_215, 6_330_000, 6_700_000, 4_470_000, 2_980_000]
    return sorted(set(edges + near + sizes + rng.integers(1, 1 << 24, 2_000).tolist()))


def test_row_class_covers_and_is_monotone():
    ns = _row_counts()
    classes = [row_class(n) for n in ns]
    assert all(c >= n for n, c in zip(ns, classes))
    assert all(a <= b for a, b in zip(classes, classes[1:]))
    assert all(row_class(c) == c for c in classes)  # a class is its own class


def test_row_class_ladder_is_tiled_and_wastes_at_most_an_eighth():
    for n in _row_counts():
        c = row_class(n)
        if n <= ROW_LADDER_FROM:
            assert c == next_pow2(n)
        else:
            assert c % ROW_TILE == 0 and (c - n) / n <= 0.125, n


@pytest.mark.parametrize("n,want", [(6_001_215, 6_291_456), (5_760_000, 5_767_168),
                                    (6_700_000, 6_815_744), (4_470_000, 4_718_592),
                                    (2_980_000, 3_145_728), (1 << 23, 1 << 23)])
def test_row_class_of_the_cells_instances(n, want):
    assert row_class(n) == want


@pytest.mark.parametrize("n,want", [(9, 16), (127, 128), (128, 128), (2_526, 4_096),
                                    (9_000, 10_240), (10_000, 10_240), (10_395, 12_288),
                                    (16_385, 32_768)])
def test_group_class_is_the_kernels_padded_count(n, want):
    assert group_class(n) == padded_groups(n) == want


def test_group_class_covers_and_keeps_the_kernels_tiling():
    """Every class is at least its count, classes rise with the count, a
    class is its own class, and the kernel tiles the class as it tiles the
    count: the padded groups cost nothing the count did not."""
    ns = list(range(1, 3_000)) + list(range(3_000, 70_000, 37))
    classes = [group_class(n) for n in ns]
    assert all(c >= n for n, c in zip(ns, classes))
    assert all(a <= b for a, b in zip(classes, classes[1:]))
    for n, c in zip(ns, classes):
        assert group_class(c) == c
        lo, _, hi_block, n_gblocks = _group_tiling(n)
        assert _group_tiling(c)[0] == lo and _group_tiling(c)[2:] == (hi_block, n_gblocks)
    assert all(c == next_pow2(n) for n, c in zip(ns, classes) if n <= 128)


def _inputs(n, g, values, rng):
    """``n`` rows over ``g`` groups whose per-group sums are exact in float32
    in any order: integers, or sixteenths (float32 values that are not
    integers), small enough for the group sizes."""
    per_group = -(-n // g)
    top = min(4096, (1 << 23) // per_group)
    gid = rng.integers(0, g, n).astype(np.int32)
    m = rng.integers(-top, top + 1, n)
    vals = (m if values == "integral" else m / 16).astype(np.float32)
    return gid, vals, rng.random(n) < 0.7


@pytest.mark.parametrize("values", ["integral", "float32"])
@pytest.mark.parametrize("tail", [False, True])
@pytest.mark.parametrize("n,g", [(140_001, 10_000), (180_000, 5_400), (132_000, 9)])
def test_segment_sums_counts_at_ladder_classes_is_exact(n, g, values, tail):
    """The interpret-mode kernel at a row class and a group class that are
    not powers of two, with a WHERE mask, over rows padded by the executor
    (a scan) or carrying an instance's ``PAD_VALID`` tail (row 0 repeated,
    weight 0): bit for bit the reference over the real rows alone."""
    assert row_class(n) & (row_class(n) - 1)  # not a power of two
    rng = np.random.default_rng(n + g)
    gid, vals, where = _inputs(n, g, values, rng)
    want_s, want_c = ref.segment_aggregate_ref(
        jnp.asarray(vals), jnp.asarray(gid), g, jnp.asarray(where))
    w = where
    if tail:
        idx = np.concatenate([np.arange(n), np.zeros(row_class(n) - n, np.int64)])
        valid = np.arange(idx.shape[0]) < n
        gid, vals, w = gid[idx], vals[idx], where[idx] & valid
    with ops.forced_backend("interpret"):
        s, c = segment_sums_counts(jnp.asarray(vals), jnp.asarray(gid), g, jnp.asarray(w))
    assert s.shape == c.shape == (g,)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(want_c))
