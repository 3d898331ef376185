"""With the timed path broken underneath, a rehearsal's ``correct`` comes
out false: one test per fault the cells can have (on one chip there is no
exchange between chips to leave out)."""
import dataclasses

import numpy as np
import pytest

from chipbench_util import BENCH, TOY_CELLS, add_toy_join_cells, copy_benchmark, rehearse

CELL = BENCH["workloads"][0]["name"]


def _wrap_run_batch(monkeypatch, change):
    from repro.core.engine import PBDSEngine

    orig = PBDSEngine.run_batch
    state = {}

    def run_batch(self, qs):
        return change(orig(self, qs), qs, state)

    monkeypatch.setattr(PBDSEngine, "run_batch", run_batch)


def _altered(out, qs, state):
    """An answer altered where it is produced: one value off by one."""
    fixed = []
    for res, info in out:
        if len(res.values):
            v = np.array(res.values, copy=True)
            v[0] += 1.0
            res = dataclasses.replace(res, values=v)
        fixed.append((res, info))
    return fixed


def _stale(out, qs, state):
    """State left unchanged: each template keeps the first answer it gave,
    whatever threshold a later query asks for."""
    fixed = []
    for q, (res, info) in zip(qs, out):
        key = q.inner_signature()
        fixed.append((state.setdefault(key, res), info))
    return fixed


def _half_answered(out, qs, state):
    """Half of each batch left out (all of a batch of one)."""
    return out[: len(out) // 2]


@pytest.mark.parametrize("fault", [_altered, _stale, _half_answered],
                         ids=["answer-altered", "state-unchanged", "half-batch-dropped"])
def test_broken_run_batch_is_not_correct(capsys, monkeypatch, fault):
    _wrap_run_batch(monkeypatch, fault)
    rc, line = rehearse(capsys, CELL, seed=21, seconds=2)
    assert rc == 0 and line["correct"] is False


def test_half_the_rows_mean_over_the_rest_is_not_correct(capsys, monkeypatch):
    """The aggregation reads half its rows and scales up."""
    from repro.core import queries

    orig = queries.segment_sums_counts

    def half(values, gid, n_groups, weights=None):
        import jax.numpy as jnp

        n = values.shape[0]
        w = jnp.ones(n, jnp.float32) if weights is None else weights.astype(jnp.float32)
        w = w * (jnp.arange(n) < n // 2)
        s, c = orig(values, gid, n_groups, w)
        return s * 2.0, c * 2.0

    monkeypatch.setattr(queries, "segment_sums_counts", half)
    rc, line = rehearse(capsys, CELL, seed=22)
    assert rc == 0 and line["correct"] is False


def test_wrong_hit_is_not_correct(capsys, monkeypatch):
    """The probe serves a query its sketch does not subsume: captures at the
    0.99 quantile answer the window's lower thresholds."""
    from benchmarks.chip import harness
    from repro.core import index

    load = harness.load_cell

    def tightened(root, name):
        cell = load(root, name)
        cell.traffic = dict(cell.traffic, setup_capture_quantile=0.995)
        return cell

    monkeypatch.setattr(harness, "load_cell", tightened)
    monkeypatch.setattr(index, "subsumes", lambda q1, q2: index._pred_key(q1) == index._pred_key(q2))
    rc, line = rehearse(capsys, CELL, seed=23, seconds=2)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_groups"]["value"] > 0


@pytest.mark.parametrize("cell", TOY_CELLS)
def test_unmatched_fact_rows_kept_is_not_correct(capsys, monkeypatch, tmp_path, cell):
    """The join keeps the fact rows that have no partner, pairing each with
    the dimension's first row, in a join cell and its NO-PS twin."""
    from repro.core import catalog

    bench = copy_benchmark(tmp_path)
    add_toy_join_cells(tmp_path, bench)
    orig = catalog.join_rows

    def keep_unmatched(fact_cols, right, left_key, right_key):
        lk, rk = np.asarray(fact_cols[left_key]), np.asarray(right[right_key])
        paired = dict(fact_cols, **{left_key: np.where(np.isin(lk, rk), lk, rk[0])})
        return orig(paired, right, left_key, right_key)

    monkeypatch.setattr(catalog, "join_rows", keep_unmatched)
    rc, line = rehearse(capsys, cell, seed=24, root=str(tmp_path))
    assert rc == 0 and line["correct"] is False

