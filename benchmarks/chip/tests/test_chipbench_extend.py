"""A new configuration, generator, traffic mix and per-layer metric are files
plus ``BENCHMARK.json`` entries: the harness finds them by name, with no edit
to any file it already has."""
import json
import os

from chipbench_util import (ROOT, TOY_CELLS, add_toy_join_cells, copy_benchmark,
                            rehearse)

NEW_METRIC = '''"""Share of the window's answered queries served as index hits."""
LAYER = "index probe (core/index.py)"
UNIT = "share"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    infos = [i for i in run.window_infos if i is not None]
    return sum(i.reused for i in infos) / len(infos) if infos else None
'''


def test_new_files_and_entries_add_a_cell(capsys, tmp_path):
    bench = copy_benchmark(tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}

    with open(chip / "configs" / "crimes-synth-6.7m.json") as f:
        config = json.load(f)
    config.update(name="crimes-1m", generator={"name": "crimes", "rows": {"rows": 1_000_000}})
    (chip / "configs" / "crimes-1m.json").write_text(json.dumps(config))
    with open(chip / "traffic" / "qagh-hits.json") as f:
        mix = json.load(f)
    mix.update(templates=mix["templates"][:2], having={"op": ">", "quantile": [0.95, 0.99]})
    (chip / "traffic" / "two-panels.json").write_text(json.dumps(mix))
    (chip / "metrics" / "hit_share.py").write_text(NEW_METRIC)

    bench["configs"].append({"name": "crimes-1m", "source": "test",
                             "file": "benchmarks/chip/configs/crimes-1m.json",
                             "reduced": ["generator"], "why": "test"})
    bench["workloads"].append({"name": "crimes-1m-two-panels", "config": "crimes-1m",
                               "traffic": "two-panels", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "hit_share", "unit": "share", "better": "higher",
                               "source": "program_counter", "layer": "index probe (core/index.py)",
                               "moves": "p50_ms", "workloads": ["crimes-1m-two-panels"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, line = rehearse(capsys, "crimes-1m-two-panels", root=str(tmp_path))
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    rc, line = rehearse(capsys, "crimes-1m-two-panels", trace=1, root=str(tmp_path))
    # The other per-layer metrics list their cells; only the new one applies.
    assert rc == 0 and line["metrics"] == {"hit_share": {"value": 1.0, "unit": "share"}}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_new_files_add_a_join_configuration_and_its_nops_twin(capsys, tmp_path):
    """A two-table generator, its configuration, a join template and the same
    traffic with the engine's ``NO-PS`` override: the join cell answers from
    sketches, its twin from scans, and both are correct."""
    bench = copy_benchmark(tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "metrics" / "hit_share.py").write_text(NEW_METRIC)
    bench["per_layer"].append({"name": "hit_share", "unit": "share", "better": "higher",
                               "source": "program_counter", "layer": "index probe (core/index.py)",
                               "moves": "p50_ms", "workloads": list(TOY_CELLS)})
    add_toy_join_cells(tmp_path, bench)

    for cell, hit_share in zip(TOY_CELLS, (1.0, 0.0)):
        rc, line = rehearse(capsys, cell, seed=2**31 + 5, root=str(tmp_path))
        assert rc == 0 and line["correct"] is True and line["attempted"] == 4
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        rc, line = rehearse(capsys, cell, seed=6, trace=1, root=str(tmp_path))
        assert rc == 0 and line["correct"] is True
        assert line["metrics"] == {"hit_share": {"value": hit_share, "unit": "share"}}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_metric_files_agree_with_benchmark_json():
    """Each per-layer metric's reader states the layer, unit, source and
    end-to-end metric that BENCHMARK.json gives it."""
    from benchmarks.chip import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(ROOT, bench["workloads"][0]["name"])
    for m in bench["per_layer"]:
        mod = cell.metric_module(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
