"""The trace reduction on a small recorded trace whose answers are worked
out by hand: a 1000 ns window, device ops over [100, 300], [500, 600] and
[900, 950] (two of them overlapping, one outside the window), the
segment-aggregate program over [100, 300] and [900, 950]."""
import os

import pytest

from chipbench_util import ROOT  # noqa: F401  (puts the checkout on the path)

from benchmarks.chip import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_small.json")
TABLE = {"segment_aggregate": {"line": "XLA Modules", "pattern": "_segment_aggregate_jit"}}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(trace_reduce.read_events(DATA), TABLE)


def test_idle_share_and_kernel_time(summary):
    assert summary.window_s == pytest.approx(1000e-9)
    assert summary.busy_s == pytest.approx(350e-9)  # union, clipped to the window
    assert 1 - summary.busy_s / summary.window_s == pytest.approx(0.65)
    assert summary.kernel_s == {"segment_aggregate": pytest.approx(250e-9)}


def test_breakdown(summary):
    ops = {name: s for name, s in summary.device_ops}
    assert ops == {"fusion.1": pytest.approx(150e-9),
                   "custom-call.2": pytest.approx(150e-9),
                   "copy.3": pytest.approx(100e-9)}
    # Gaps [50,100], [300,500], [600,900], [950,1050], each labelled by the
    # host span that overlaps it most.
    assert [name for name, _ in summary.idle_gaps] == ["wait", "run_batch"]
    assert dict(summary.idle_gaps) == {"wait": pytest.approx(500e-9),
                                       "run_batch": pytest.approx(150e-9)}


def test_busy_time_averages_over_devices():
    ev = trace_reduce.read_events(DATA)
    ev.append(trace_reduce.Event("/device:TPU:1", "XLA Ops", "fusion.9", 100, 500))
    s = trace_reduce.summarize(ev, TABLE)
    assert s.busy_s == pytest.approx((350e-9 + 500e-9) / 2)


def test_nothing_to_read_without_a_window_or_a_kernel():
    ev = [e for e in trace_reduce.read_events(DATA) if e.name != "bench.window"]
    assert trace_reduce.summarize(ev, TABLE) is None
    s = trace_reduce.summarize(trace_reduce.read_events(DATA),
                               {"other": {"line": "XLA Ops", "pattern": "^nothing$"}})
    assert s.kernel_s == {}


def test_recorded_v5e_excerpt():
    """40 ms of a traced all-hits crimes window on one v5e (times rebased
    to the excerpt's start).  The expected busy and kernel times were also
    read off a 1 ns boolean timeline of the same events."""
    path = os.path.join(os.path.dirname(DATA), "trace_v5e_hits.json")
    s = trace_reduce.summarize(trace_reduce.read_events(path), TABLE)
    assert s.window_s == pytest.approx(0.04)
    assert s.busy_s == pytest.approx(0.002205585, abs=1e-12)
    assert s.kernel_s == {"segment_aggregate": pytest.approx(0.0020371, abs=1e-12)}
    assert dict(s.idle_gaps) == {"run_batch": pytest.approx(0.024547785, abs=1e-12),
                                 "wait": pytest.approx(0.01324663, abs=1e-12)}
    assert s.device_ops[0][0] == "_segment_aggregate_jit.1"
