"""Shared helpers of the benchmark's tests: put the checkout and its
``src`` on the path, and run one rehearsal in this process."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def rehearse(capsys, workload, seed=11, seconds=1, trace=0, root=ROOT):
    """Run one cell's rehearsal; return (exit code, last stdout line or None)."""
    from benchmarks.chip import harness

    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace), "--rehearse"], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def copy_benchmark(tmp_path):
    """A checkout at ``tmp_path`` holding this benchmark's files and the
    system under test (linked); returns its ``BENCHMARK.json`` as a dict."""
    import shutil

    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    return json.loads(json.dumps(BENCH))


# A two-table configuration as new files: a fact table whose foreign key
# misses the 100-row dimension for about a sixth of its rows, one join
# template grouped on a dimension attribute, and its NO-PS twin.
TOY_GENERATOR = '''"""Toy star: sales with a foreign key into 100 stores; about a sixth of
the keys have no store.  Sales of the first quarter of the stores are larger,
so a sketch on the key pays."""
import numpy as np


def generate(rng, facts, stores):
    key = rng.integers(1, stores + stores // 5 + 1, facts)
    amount = np.minimum(rng.zipf(1.6, facts), 200) * np.where(key <= stores // 4, 8, 1)
    return {
        "sales": ({"s_store": key.astype(np.int32),
                   "s_day": rng.integers(1, 29, facts).astype(np.int32),
                   "s_amount": amount.astype(np.int32)}, ("s_day",)),
        "stores": ({"st_key": np.arange(1, stores + 1, dtype=np.int32),
                    "st_region": rng.integers(1, 13, stores).astype(np.int32)},
                   ("st_key",)),
    }
'''
TOY_CONFIG = {"name": "toy-star", "generator": {"name": "toy_star",
                                                "rows": {"facts": 4000, "stores": 100}},
              "engine": {"strategy": "CB-OPT-REL", "n_ranges": 100, "theta": 0.05,
                         "cluster_tables": False}}
TOY_JOIN = {
    "templates": [{"name": "J1", "table": "sales", "groupby": ["st_region"],
                   "agg": ["sum", "s_amount"],
                   "join": {"right": "stores", "left_key": "s_store", "right_key": "st_key"}}],
    "popularity": {"zipf_s": 0.0}, "having": {"op": ">", "quantile": [0.5, 0.95]},
    "setup_capture_quantile": 0.5, "schedule_seed": 0, "rate_qps": 4.0,
    "checks": {"max_rel_err": 1e-5, "wrong_groups": 0, "unanswered": 0}}
TOY_CELLS = ("toy-star-join", "toy-star-join-nops")


def add_toy_join_cells(tmp_path, bench):
    """Write the toy configuration, its two traffic files and their cells
    into the checkout at ``tmp_path`` (``BENCHMARK.json`` included)."""
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "generators" / "toy_star.py").write_text(TOY_GENERATOR)
    (chip / "configs" / "toy-star.json").write_text(json.dumps(TOY_CONFIG))
    (chip / "traffic" / "toy-join.json").write_text(json.dumps(TOY_JOIN))
    nops = dict(TOY_JOIN, engine={"strategy": "NO-PS"}, setup_capture_quantile=None)
    (chip / "traffic" / "toy-join-nops.json").write_text(json.dumps(nops))
    bench["configs"].append({"name": "toy-star", "source": "test",
                             "file": "benchmarks/chip/configs/toy-star.json",
                             "reduced": [], "why": "test"})
    for cell, mix in zip(TOY_CELLS, ("toy-join", "toy-join-nops")):
        bench["workloads"].append({"name": cell, "config": "toy-star", "traffic": mix,
                                   "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
