"""The readers of the program's own spans and counters (``RunInfo.trace``) on
hand-built records, ``None`` from a program whose ``RunInfo`` carries no
record, and their values in a traced rehearsal of a copy of the benchmark that
lists them."""
import dataclasses
import json
import os
import shutil

import pytest

from chipbench_util import ROOT, rehearse

from benchmarks.chip import harness
from repro.core.engine import RunInfo
from repro.runtime.trace import Record, Span

MS = 1_000_000  # ns
NEW = ("device_wait_ms", "hit_host_ms", "host_syncs_per_query", "d2h_mb_per_query",
       "batch_wait_ms", "scan_s.setup")
HIT = "hit execution and scan (core/queries.py, core/sketch.py)"
# The per-layer entries that list these readers for the cell.
ENTRIES = [
    {"name": "device_wait_ms", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": HIT, "moves": "p50_ms", "workloads": ["crimes-synth-qagh-hits"]},
    {"name": "hit_host_ms", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": HIT, "moves": "p50_ms", "workloads": ["crimes-synth-qagh-hits"]},
    {"name": "host_syncs_per_query", "unit": "count", "better": "lower",
     "source": "program_counter", "layer": HIT, "moves": "p50_ms",
     "workloads": ["crimes-synth-qagh-hits"]},
    {"name": "d2h_mb_per_query", "unit": "MB", "better": "lower", "source": "program_counter",
     "layer": HIT, "moves": "p50_ms", "workloads": ["crimes-synth-qagh-hits"]},
    {"name": "batch_wait_ms", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": "entry (core/engine.py run_batch)", "moves": "p95_ms",
     "workloads": ["crimes-synth-qagh-hits"]},
    {"name": "scan_s.setup", "unit": "s", "better": "lower", "source": "program_span",
     "layer": HIT, "moves": "setup_s", "workloads": ["crimes-synth-qagh-hits"]},
]


def _cell():
    return harness.load_cell(ROOT, "crimes-synth-qagh-hits")


def _read(name, run):
    return _cell().metric_module(name).read(run)


def _hit(batch_span, pos, serve, waits, syncs, nbytes):
    rec = Record(batch=7, pos=pos, host_syncs=syncs, d2h_bytes=nbytes)
    s = Span("repro.serve", serve[0] * MS, serve[1] * MS, batch_span)
    rec.spans.append(s)
    rec.spans.append(Span("repro.probe", serve[0] * MS, (serve[0] + 1) * MS, s))
    rec.spans += [Span("repro.to_host", a * MS, b * MS, s) for a, b in waits]
    return RunInfo(True, False, "ward", "CB-OPT-GB", 0.5, trace=rec)


def _capture(batch_span, pos, shared, own_scan=None):
    rec = Record(batch=1, pos=pos)
    rec.spans.append(Span("repro.serve", 0, MS, batch_span))
    for s in shared:
        rec.share(s, 2)
    if own_scan is not None:
        rec.spans.append(own_scan)
    return RunInfo(False, True, "ward", "CB-OPT-GB", 0.5, trace=rec)


def _run(window_infos, setup_infos):
    return harness.RunRecord(stream=None, setup_infos=setup_infos,
                             window_infos=window_infos, batches=[[0, 1]], compiles=0,
                             trace=None, live_rows={}, groups=[], device_kind="cpu")


@pytest.fixture
def run():
    call = Span("repro.run_batch", 0, 100 * MS)
    window = [_hit(call, 0, (10, 60), [(40, 50), (50, 52)], 2, 3_000_000),
              _hit(call, 1, (60, 100), [(70, 90)], 1, 1_000_000), None]
    setup_call = Span("repro.run_batch", 0, 5_000 * MS)
    shared_scan = Span("repro.scan", 0, 2_000 * MS, setup_call)
    own_scan = Span("repro.scan", 2_000 * MS, 3_000 * MS, setup_call)
    setup = [_capture(setup_call, 0, [shared_scan]), _capture(setup_call, 1, [shared_scan]),
             _capture(setup_call, 2, [], own_scan)]
    return _run(window, setup)


@pytest.mark.parametrize("name,want", [
    ("device_wait_ms", (12 + 20) / 2),        # the to_host spans of each query
    ("hit_host_ms", ((50 - 12) + (40 - 20)) / 2),  # serve less to_host
    ("host_syncs_per_query", 1.5),
    ("d2h_mb_per_query", 2.0),
    ("batch_wait_ms", (10 + 60) / 2),         # run_batch start to own serve start
    ("scan_s.setup", 3.0),                    # the shared scan counted once
])
def test_reader_on_a_hand_built_record(run, name, want):
    assert _read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_without_a_record_reads_nothing(name):
    """What a program without ``RunInfo.trace`` leaves: a ``RunInfo`` whose
    ``trace`` is ``None``, or one with no such attribute at all."""

    @dataclasses.dataclass
    class OldRunInfo:
        reused: bool = True
        t_probe: float = 1e-5
        t_execute: float = 0.1
        t_select: float = 1.0
        t_capture: float = 2.0

    for infos in ([RunInfo(True, False, "ward", "CB-OPT-GB", 0.5)], [OldRunInfo()]):
        assert _read(name, _run(infos + [None], list(infos))) is None
    assert _read(name, _run([], [])) is None


def test_new_readers_are_listed_for_the_cell(capsys, tmp_path):
    """Listed for the cell, as ``ENTRIES`` gives them, the readers report in a
    traced rehearsal, each with the layer, unit, source and end-to-end metric
    of its entry; the checkout's own files are left as they were."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    bench["per_layer"] += ENTRIES
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(str(tmp_path), "crimes-synth-qagh-hits")
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    for m in ENTRIES:
        mod = cell.metric_module(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
    rc, line = rehearse(capsys, "crimes-synth-qagh-hits", seed=5, trace=1, root=str(tmp_path))
    assert rc == 0 and line["correct"] is True
    for m in ENTRIES:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0, m["name"]
