"""Each cell's CPU rehearsal prints a well-formed last line, and so does each
rung of the sweep; a run without a chip, or without the system under test,
prints none."""
import json
import os
import shutil

import pytest

from chipbench_util import BENCH, LINE_KEYS, ROOT, rehearse

from benchmarks.chip import harness

CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(capsys, cell):
    rc, line = rehearse(capsys, cell, seed=2**31 + 17)
    assert rc == 0
    assert all(k in line for k in LINE_KEYS)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    with open(os.path.join(ROOT, "benchmarks", "chip", "traffic", f"{w['traffic']}.json")) as f:
        rate = min(json.load(f)["rate_qps"], harness.REHEARSE_RATE)
    assert line["attempted"] == max(1, round(rate))  # one second at the rehearsal's rate
    want = {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want
    for m in BENCH["end_to_end"]:
        if m["name"] in want:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
            assert line["metrics"][m["name"]]["value"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert set(line["checks"]) == {"max_rel_err", "wrong_groups", "unanswered"}


# What a traced CPU rehearsal reads per cell.  The CPU has no device plane:
# the trace metrics have nothing to read and are left out; the program's
# spans and counters are there.
TRACED = {
    "crimes-synth-qagh-hits": {"probe_us", "execute_ms", "sketch_rows_share",
                               "select_s.setup", "capture_s.setup", "window_compiles",
                               "window_catalog_misses", "device_wait_ms", "hit_host_ms",
                               "host_syncs_per_query", "d2h_mb_per_query",
                               "batch_wait_ms", "scan_s.setup"},
    "crimes-synth-qagh-nops": {"execute_ms", "window_compiles", "window_catalog_misses"},
}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_per_layer_metrics(capsys, cell):
    rc, line = rehearse(capsys, cell, seed=5, trace=1)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == TRACED[cell]
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["metrics"]["window_catalog_misses"]["value"] == 0
    if "sketch_rows_share" in TRACED[cell]:
        assert 0 < line["metrics"]["sketch_rows_share"]["value"] < 1
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_means_no_result(capsys):
    from benchmarks.chip import harness

    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_benchmark_files_alone_give_no_result(capsys, tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    rc, line = rehearse(capsys, CELLS[0], root=str(tmp_path))
    assert rc != 0 and line is None


def test_sweep_rehearsal_stops_at_the_first_rate_not_sustained(capsys):
    from benchmarks.chip import sweep

    # The CPU serves a hit in about 20 ms: 200 q/s is far past its knee (at
    # 40 q/s, near it, a rung was sometimes sustained).
    rc = sweep.main(["--workload", CELLS[0], "--seed", "7", "--rates", "1,200,400",
                     "--seconds", "1", "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and "setup" in lines[0]
    rungs = lines[1:]
    assert [r["offered_qps"] for r in rungs] == [1.0, 200.0]
    assert rungs[0]["sustained_qps"] == 1.0
    assert rungs[1]["sustained_qps"] < 0.9 * 200 and rungs[1]["drain_s"] > 0
    assert all(r["correct"] for r in rungs)
