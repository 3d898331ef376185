"""Infrastructure layers: sharding rules, checkpointing, elastic planning,
straggler detection, retries, data pipeline, HLO stats parser."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.models.params import P
from repro.parallel.sharding import batch_axes, spec_for


class FakeMesh:
    """Duck-typed mesh for rule tests (axis_names + devices.shape)."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)


MESH = FakeMesh((16, 16), ("data", "model"))
MESH3 = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_for_fsdp_tp():
    p = P((4096, 32, 128), ("embed", "q_heads", "head_dim"))
    assert spec_for(p, MESH) == PartitionSpec("data", "model", None)
    assert spec_for(p, MESH3) == PartitionSpec(("pod", "data"), "model", None)


def test_spec_for_indivisible_replicates():
    p = P((4096, 40, 128), ("embed", "q_heads", "head_dim"))  # 40 % 16 != 0
    assert spec_for(p, MESH) == PartitionSpec("data", None, None)
    p2 = P((100, 7), ("embed", "ffn"))  # 100 % 16 != 0, 7 % 16 != 0
    assert spec_for(p2, MESH) == PartitionSpec(None, None)


def test_spec_for_never_reuses_axis():
    p = P((2048, 2048), ("ffn", "ffn"))
    s = spec_for(p, MESH)
    axes = [a for a in s if a is not None]
    assert len(axes) <= 1


def test_batch_axes():
    assert batch_axes(MESH, 64) == "data"
    assert batch_axes(MESH, 7) is None
    assert batch_axes(MESH3, 64) == ("pod", "data")
    assert batch_axes(MESH3, 16) == "data"  # not divisible by 32, but by 16


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager

    state = {"w": jnp.arange(12.0).reshape(3, 4), "step": jnp.array(7)}
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    mgr.save(1, state, extra={"step": 1})
    mgr.save(2, jax.tree_util.tree_map(lambda x: x + 1, state), extra={"step": 2})
    mgr.save(3, jax.tree_util.tree_map(lambda x: x + 2, state), extra={"step": 3})
    assert mgr.all_steps() == [2, 3]  # keep-last-2 GC
    restored, extra = mgr.restore(state)
    assert extra["step"] == 3
    np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(12.0).reshape(3, 4) + 2)
    # no stray tmp dirs (atomic publish)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_checkpoint_resume_training_equivalence(tmp_path):
    """Train 4 steps straight == train 2, checkpoint, restore, train 2."""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.optim.adamw import OptConfig
    from repro.train.step import TrainSpec, init_train_state, make_train_step, microbatch_reshape

    cfg = get_config("stablelm-1.6b", smoke=True)
    spec = TrainSpec(microbatch=1, opt=OptConfig(total_steps=10))
    step = jax.jit(make_train_step(cfg, spec))

    def batches(n):
        return [
            microbatch_reshape(
                {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i), (2, 16), 0, cfg.vocab_size)}, 1
            )
            for i in range(n)
        ]

    bs = batches(4)
    s_a = init_train_state(jax.random.PRNGKey(1), cfg, spec)
    for b in bs:
        s_a, _ = step(s_a, b)

    s_b = init_train_state(jax.random.PRNGKey(1), cfg, spec)
    for b in bs[:2]:
        s_b, _ = step(s_b, b)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(2, s_b)
    s_b2, _ = mgr.restore(s_b)
    for b in bs[2:]:
        s_b2, _ = step(s_b2, b)

    la = jax.tree_util.tree_leaves(s_a["params"])
    lb = jax.tree_util.tree_leaves(s_b2["params"])
    for a, b in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6)


def test_elastic_remesh_planning():
    from repro.runtime import feasible_mesh_shape, plan_remesh

    # full fleet
    assert feasible_mesh_shape(256, 16) == (16, 16)
    # lose a host (8 devices): keep TP=16, shrink DP
    shape = feasible_mesh_shape(248, 16)
    assert shape == (15, 16)
    # multi-pod preference
    assert feasible_mesh_shape(512, 16, prefer_pods=2) == (2, 16, 16)
    plan = plan_remesh(248, 16, global_batch=256, old_n_micro=4, old_data_extent=16)
    assert plan is not None
    assert plan.mesh_shape == (15, 16)
    mb = 256 // plan.n_micro
    assert mb % 15 == 0 or plan.n_micro == 256  # microbatch shardable on new DP
    # catastrophic loss: fewer devices than TP extent
    assert feasible_mesh_shape(8, 16) is None


def test_straggler_monitor():
    from repro.runtime import StragglerMonitor

    mon = StragglerMonitor(window=16, threshold=2.0)
    flags = [mon.observe(0.1) for _ in range(10)]
    assert not any(flags)
    assert mon.observe(0.5)  # 5x median
    assert not mon.observe(0.11)


def test_retries():
    from repro.runtime import RetryPolicy, with_retries

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert with_retries(flaky, RetryPolicy(max_attempts=3, backoff_s=0.0)) == "ok"
    assert calls["n"] == 3
    with pytest.raises(RuntimeError):
        with_retries(lambda: (_ for _ in ()).throw(RuntimeError("x")).__next__(),
                     RetryPolicy(max_attempts=2, backoff_s=0.0))


def test_data_pipeline_determinism_and_skipping():
    from repro.data import CurationSpec, SketchedDataPipeline, make_corpus_metadata
    from repro.core.queries import provenance_mask

    meta = make_corpus_metadata(n_docs=3_000, seed=1)
    spec = CurationSpec(having_value=0.55)
    p1 = SketchedDataPipeline(meta, spec, 8, 32, 1000, seed=42)
    p2 = SketchedDataPipeline(meta, spec, 8, 32, 1000, seed=42)
    b1, b2 = next(iter(p1)), next(iter(p2))
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert 0.0 < p1.skipped_fraction < 1.0
    # Sketch-selected docs are a superset of the exact curation provenance.
    from repro.core.table import Database

    prov = provenance_mask(spec.query(), Database({"corpus": meta}))
    prov_docs = set(np.asarray(meta["doc_id"])[prov].tolist())
    assert prov_docs <= set(p1.selected_docs.tolist())
    # Regression: padded sketch instances duplicate masked rows — the
    # pipeline must filter them out, never oversample a document.
    assert len(p1.selected_docs) == len(set(p1.selected_docs.tolist()))


def test_data_pipeline_resume():
    from repro.data import CurationSpec, SketchedDataPipeline, make_corpus_metadata

    meta = make_corpus_metadata(n_docs=2_000, seed=2)
    p1 = SketchedDataPipeline(meta, CurationSpec(), 4, 16, 1000, seed=7)
    it = iter(p1)
    next(it)
    st = p1.state()
    want = next(it)
    p2 = SketchedDataPipeline(meta, CurationSpec(), 4, 16, 1000, seed=7)
    p2.restore(st)
    got = next(iter(p2))
    np.testing.assert_array_equal(want["tokens"], got["tokens"])


def test_dp_rank_sharding_disjoint():
    from repro.data import CurationSpec, SketchedDataPipeline, make_corpus_metadata

    meta = make_corpus_metadata(n_docs=2_000, seed=3)
    parts = []
    for r in range(4):
        p = SketchedDataPipeline(meta, CurationSpec(), 16, 8, 1000, dp_rank=r, dp_size=4, seed=5)
        parts.append(next(iter(p))["tokens"])
    stacked = np.concatenate(parts, 0)
    assert len(np.unique(stacked[:, 0])) >= len(stacked) // 2  # mostly distinct docs


def test_hlo_stats_parser():
    from repro.launch.hlo_stats import analyze_hlo

    hlo = """
HloModule test

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %ar = f32[8,128] all-reduce(%gte), channel_id=1, to_apply=%sum
  ROOT %t = (s32[], f32[8,128]) tuple(%i, %ar)
}

%cond (p2: (s32[], f32[8,128])) -> pred[] {
  %p2 = (s32[], f32[8,128]) parameter(0)
  %c = s32[] constant(24)
  ROOT %cmp = pred[] compare(%gte2, %c), direction=LT
}

ENTRY %main (a: f32[128,256], b: f32[256,64]) -> f32[128,64] {
  %a = f32[128,256] parameter(0)
  %b = f32[256,64] parameter(1)
  %w = (s32[], f32[8,128]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"24"}}
  ROOT %d = f32[128,64] dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    res = analyze_hlo(hlo)
    # dot: 2*128*64*256 flops
    assert res["dot_flops"] == 2 * 128 * 64 * 256
    # all-reduce inside 24-trip while: 24 * 8*128*4 bytes
    assert res["collective_bytes"] == 24 * 8 * 128 * 4
