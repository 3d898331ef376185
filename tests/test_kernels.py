"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes & dtypes.
Pallas kernels run in interpret mode on CPU (the TPU lowering is exercised on
real hardware; interpret mode executes the same kernel body)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [17, 1000, 5000])
@pytest.mark.parametrize("n_ranges", [3, 100, 1000])
def test_fragment_bitmap(n, n_ranges):
    bucket = jnp.asarray(RNG.integers(0, n_ranges, n).astype(np.int32))
    prov = jnp.asarray(RNG.random(n) < 0.05)
    got = ops.fragment_bitmap(prov, bucket, n_ranges, backend="interpret")
    want = ref.fragment_bitmap_ref(prov, bucket, n_ranges)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fragment_bitmap_empty_provenance():
    bucket = jnp.asarray(RNG.integers(0, 10, 100).astype(np.int32))
    prov = jnp.zeros(100, bool)
    got = ops.fragment_bitmap(prov, bucket, 10, backend="interpret")
    assert not np.asarray(got).any()


@pytest.mark.parametrize("n", [64, 2048, 4097])
@pytest.mark.parametrize("n_ranges", [7, 129, 1000])
def test_sketch_filter(n, n_ranges):
    bucket = jnp.asarray(RNG.integers(0, n_ranges, n).astype(np.int32))
    bits = jnp.asarray(RNG.random(n_ranges) < 0.4)
    got = ops.sketch_filter(bucket, bits, backend="interpret")
    want = ref.sketch_filter_ref(bucket, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,g", [(100, 5), (3000, 700), (2048, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_segment_aggregate(n, g, dtype):
    gid = jnp.asarray(RNG.integers(0, g, n).astype(np.int32))
    vals = jnp.asarray(RNG.normal(0, 10, n).astype(dtype))
    w = jnp.asarray((RNG.random(n) < 0.5).astype(np.float32))
    s1, c1 = ops.segment_aggregate(vals, gid, g, w, backend="interpret")
    s2, c2 = ref.segment_aggregate_ref(vals, gid, g, w)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)


@pytest.mark.parametrize("b,n,g", [(1, 100, 5), (4, 700, 130), (3, 2048, 512)])
def test_segment_aggregate_batch(b, n, g):
    """Batched kernel == ref == per-row unbatched kernel, bit-for-bit on
    integral f32 inputs (the sharded fused launch's exactness envelope)."""
    gid = jnp.asarray(RNG.integers(0, g, (b, n)).astype(np.int32))
    vals = jnp.asarray(RNG.integers(0, 100, (b, n)).astype(np.float32))
    w = jnp.asarray((RNG.random((b, n)) < 0.5).astype(np.float32))
    s1, c1 = ops.segment_aggregate_batch(vals, gid, g, w, backend="interpret")
    s2, c2 = ref.segment_aggregate_batch_ref(vals, gid, g, w)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    for i in range(b):
        s3, c3 = ops.segment_aggregate(vals[i], gid[i], g, w[i], backend="ref")
        np.testing.assert_array_equal(np.asarray(s2[i]), np.asarray(s3))
        np.testing.assert_array_equal(np.asarray(c2[i]), np.asarray(c3))


def test_segment_aggregate_matches_engine_groupby():
    """Kernel path == the executor's segment aggregation."""
    from repro.core.datasets import make_crimes
    from repro.core.table import encode_groups

    t = make_crimes(4_000, seed=2)
    gid, g, _ = encode_groups(t, ("district", "year"))
    s1, c1 = ops.segment_aggregate(t["records"], jnp.asarray(gid), g, backend="interpret")
    want = np.bincount(gid, weights=np.asarray(t["records"], np.float64), minlength=g)
    np.testing.assert_allclose(np.asarray(s1), want, rtol=1e-4)


def test_forced_backend_selects_mode_and_is_counted():
    """``forced_backend`` steers calls that pass no ``backend=``, restores
    the default on exit, and every dispatch is counted per mode."""
    bucket = jnp.asarray(RNG.integers(0, 7, 300).astype(np.int32))
    bits = jnp.asarray(RNG.random(7) < 0.5)
    before = ops.DISPATCHES[("sketch_filter", "interpret")]
    with ops.forced_backend("interpret"):
        got = ops.sketch_filter(bucket, bits)
    assert ops.DISPATCHES[("sketch_filter", "interpret")] == before + 1
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.sketch_filter_ref(bucket, bits)))
    assert ops._forced is None
    with pytest.raises(ValueError):
        with ops.forced_backend("tpu"):
            pass


@pytest.mark.parametrize("n,g", [(5000, 37), (20000, 512)])
def test_segment_aggregate_exact_above_bf16_range(n, g):
    """Integral values above 256 (not exact in bf16) with per-group sums
    below 2^24: the kernel equals a float64 reference bit for bit."""
    gid = RNG.integers(0, g, n).astype(np.int32)
    vals = RNG.integers(257, 4097, n).astype(np.float32)
    w = (RNG.random(n) < 0.75).astype(np.float32)
    want = np.bincount(gid, weights=vals.astype(np.float64) * w, minlength=g)
    for backend in ("interpret", "ref"):
        s, c = ops.segment_aggregate(jnp.asarray(vals), jnp.asarray(gid), g,
                                     jnp.asarray(w), backend=backend)
        np.testing.assert_array_equal(np.asarray(s, np.float64), want)
    sb, _ = ops.segment_aggregate_batch(
        jnp.asarray(np.stack([vals, vals[::-1]])), jnp.asarray(np.stack([gid, gid[::-1]])),
        g, jnp.asarray(np.stack([w, w[::-1]])), backend="interpret")
    np.testing.assert_array_equal(np.asarray(sb[0], np.float64), want)


# Per-group sums of |v * w| up to 2^24 keep every partial sum of every bf16
# part an integer within float32's exact range (the kernel's exactness
# envelope): at most 15 rows a group of values up to 2^20.
ROWS_PER_GROUP = 15
ENVELOPE = 2 ** 24


def _integral_segments(g, n_pad_rows, rng):
    """Signed integral values in [257, 2^20] with at most 15 rows a group,
    then ``n_pad_rows`` padded rows (gid -1, weight 0, a large value)."""
    per = min(ROWS_PER_GROUP, max(1, 12_000 // g))
    gid = rng.permutation(np.repeat(np.arange(g, dtype=np.int32), per))
    n = gid.shape[0]
    vals = (rng.integers(257, 2 ** 20 + 1, n) * rng.choice([-1, 1], n)).astype(np.float32)
    w = rng.random(n) < 0.75
    gid = np.concatenate([gid, np.full(n_pad_rows, -1, np.int32)])
    vals = np.concatenate([vals, np.full(n_pad_rows, 2.0 ** 20, np.float32)])
    w = np.concatenate([w, np.zeros(n_pad_rows, bool)])
    return gid, vals, w


@pytest.mark.parametrize("weights", ["bool", "float"])
@pytest.mark.parametrize("g", [16, 128, 129, 700, 16384, 16385, 40000])
def test_segment_aggregate_two_level_exact(g, weights):
    """Group counts on both sides of the (hi, lo) split and of the 16,384
    groups of one group block, signed integral values above bf16's exact
    range, padded rows with gid -1 and weight 0: the kernel equals a float64
    ``np.bincount`` bit for bit, whether the 0/1 mask is boolean or float."""
    rng = np.random.default_rng(g)
    gid, vals, w = _integral_segments(g, 37, rng)
    real = gid >= 0
    want = np.bincount(gid[real], weights=vals[real].astype(np.float64) * w[real],
                       minlength=g)
    want_c = np.bincount(gid[real], weights=w[real].astype(np.float64), minlength=g)
    assert np.bincount(gid[real], weights=np.abs(vals[real]) * w[real]).max() <= ENVELOPE
    wd = jnp.asarray(w if weights == "bool" else w.astype(np.float32))
    s, c = ops.segment_aggregate(jnp.asarray(vals), jnp.asarray(gid), g, wd,
                                 backend="interpret")
    np.testing.assert_array_equal(np.asarray(s, np.float64), want)
    np.testing.assert_array_equal(np.asarray(c, np.float64), want_c)


@pytest.mark.parametrize("g", [16, 129, 700, 16385, 40000])
def test_segment_aggregate_batch_two_level_exact(g):
    """B > 1 on signed integral values above 256 with padded rows: the
    batched kernel equals the batched reference and the unbatched kernel on
    each problem, bit for bit."""
    rng = np.random.default_rng(1000 + g)
    rows = [_integral_segments(g, 5 + 11 * i, rng) for i in range(3)]
    n = min(r[0].shape[0] for r in rows)
    gid, vals, w = (jnp.asarray(np.stack([r[k][:n] for r in rows])) for k in range(3))
    sb, cb = ops.segment_aggregate_batch(vals, gid, g, w, backend="interpret")
    sr, cr = ref.segment_aggregate_batch_ref(vals, gid, g, w)
    np.testing.assert_array_equal(np.asarray(sb), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(cr))
    for i in range(3):
        s1, c1 = ops.segment_aggregate(vals[i], gid[i], g, w[i], backend="interpret")
        np.testing.assert_array_equal(np.asarray(sb[i]), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(cb[i]), np.asarray(c1))


def _sum_to(total, rng):
    """Integers in [257, 2^20], with one value of any size up to 2^20 last,
    that add up to ``total``."""
    out = []
    while total > 2 ** 20:
        out.append(int(rng.integers(257, 2 ** 20 + 1)))
        total -= out[-1]
    return out + [total]


@pytest.mark.parametrize("g", [16, 700, 40000])
def test_segment_aggregate_exact_at_the_envelope(g):
    """Groups whose sum of |v| is 2^24 - 1 or 2^24 exactly, positive,
    negative and of mixed sign (the mixed group's own sum is small), in the
    unbatched and the batched kernel: still bit-identical to float64."""
    rng = np.random.default_rng(7 + g)
    groups = {0: _sum_to(2 ** 24 - 1, rng),
              g - 1: [-x for x in _sum_to(2 ** 24, rng)]}
    half = _sum_to(2 ** 23, rng)
    groups[g // 2] = half + [-x for x in _sum_to(2 ** 23, rng)]
    gid = np.concatenate([np.full(len(v), k, np.int32) for k, v in groups.items()])
    vals = np.concatenate([np.asarray(v, np.float32) for v in groups.values()])
    order = rng.permutation(gid.shape[0])
    gid, vals = gid[order], vals[order]
    assert all(np.sum(np.abs(np.asarray(v, np.float64))) <= 2 ** 24
               for v in groups.values())
    want = np.bincount(gid, weights=vals.astype(np.float64), minlength=g)
    s, c = ops.segment_aggregate(jnp.asarray(vals), jnp.asarray(gid), g,
                                 backend="interpret")
    np.testing.assert_array_equal(np.asarray(s, np.float64), want)
    np.testing.assert_array_equal(np.asarray(c, np.float64),
                                  np.bincount(gid, minlength=g))
    sb, _ = ops.segment_aggregate_batch(
        jnp.asarray(np.stack([vals, vals[::-1]])), jnp.asarray(np.stack([gid, gid[::-1]])),
        g, backend="interpret")
    np.testing.assert_array_equal(np.asarray(sb, np.float64), np.stack([want, want]))


@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_segment_aggregate_dropped_rows_add_nothing(bad):
    """A row the mask drops adds 0 to every group, whatever its value: an
    inf or NaN there spoils neither its group nor the groups of its row of
    128."""
    g, n = 300, 2000
    rng = np.random.default_rng(11)
    gid = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(257, 4097, n).astype(np.float32)
    w = rng.random(n) < 0.75
    vals[~w] = bad
    want = np.bincount(gid[w], weights=vals[w].astype(np.float64), minlength=g)
    for wd in (w, w.astype(np.float32)):
        s, c = ops.segment_aggregate(jnp.asarray(vals), jnp.asarray(gid), g,
                                     jnp.asarray(wd), backend="interpret")
        np.testing.assert_array_equal(np.asarray(s, np.float64), want)
        np.testing.assert_array_equal(np.asarray(c, np.float64),
                                      np.bincount(gid[w], minlength=g))


@pytest.mark.parametrize("s,t", [(64, 64), (96, 96), (1, 96)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(s, t, causal, window, dtype):
    if s > t:
        pytest.skip("q longer than kv")
    b, h, d = 2, 3, 64
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (b, h, t, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, backend="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_chunked_attention_matches_ref():
    """The XLA chunked (flash-schedule) attention used by the models."""
    from repro.models.layers import gqa_chunked

    b, s, hq, hkv, d = 2, 96, 8, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(4), (b, s, hq, d))
    k = jax.random.normal(jax.random.PRNGKey(5), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(6), (b, s, hkv, d))
    got = gqa_chunked(q, k, v, causal=True, chunk=32)
    # oracle via flash ref with repeated kv heads
    kr = jnp.repeat(k, hq // hkv, axis=2)
    vr = jnp.repeat(v, hq // hkv, axis=2)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3), vr.transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-3)


def test_chunked_attention_sliding_window():
    from repro.models.layers import gqa_chunked

    b, s, h, d = 1, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(7), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(8), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d))
    got = gqa_chunked(q, k, v, causal=True, window=16, chunk=32)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, window=16,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-3)


# -- stable names -------------------------------------------------------------

KERNELS_JSON = (__import__("pathlib").Path(__file__).resolve().parent.parent
                / "benchmarks" / "chip" / "kernels.json")


def _kernel_lowerings():
    n, b, groups, ranges = 1 << 14, 4, 512, 100
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    f32_b = jax.ShapeDtypeStruct((b, n), jnp.float32)
    i32_b = jax.ShapeDtypeStruct((b, n), jnp.int32)
    bool_n = jax.ShapeDtypeStruct((n,), jnp.bool_)
    bool_b = jax.ShapeDtypeStruct((b, n), jnp.bool_)
    bits = jax.ShapeDtypeStruct((ranges,), jnp.bool_)
    return {
        "segment_aggregate": (ops._segment_aggregate_jit,
                              (f32, i32, groups, f32, "pallas")),
        "segment_aggregate_batch": (ops._segment_aggregate_batch_jit,
                                    (f32_b, i32_b, groups, f32_b, "pallas")),
        "fragment_bitmap": (ops._fragment_bitmap_jit, (bool_n, i32, ranges, "pallas")),
        "fragment_bitmap_batch": (ops._fragment_bitmap_batch_jit,
                                  (bool_b, i32, ranges, "pallas")),
        "sketch_filter": (ops._sketch_filter_jit, (i32, bits, "pallas")),
    }


@pytest.mark.parametrize("kernel", ["segment_aggregate", "segment_aggregate_batch",
                                    "fragment_bitmap", "fragment_bitmap_batch",
                                    "sketch_filter"])
def test_kernel_names_are_stable(kernel):
    """Each wrapper lowers for the TPU to a module named after its jit, with
    one Mosaic kernel named after the kernel: a profiler trace finds each by
    name (``benchmarks/chip/kernels.json`` matches the module), so a rename
    fails here instead of silently emptying a metric."""
    import json
    import re

    fn, args = _kernel_lowerings()[kernel]
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert module == f"jit__{kernel}_jit"
    assert re.findall(r'kernel_name = "([^"]+)"', text) == [kernel]
    table = json.loads(KERNELS_JSON.read_text())
    for name, rule in table.items():
        assert rule["line"] == "XLA Modules"
        assert bool(re.search(rule["pattern"], module)) == (name == kernel), (name, module)
