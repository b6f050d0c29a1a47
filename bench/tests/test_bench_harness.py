"""The harness finds every cell's files by name, takes new ones without an
edit, draws traffic from the seed, and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run, trafficgen

BENCH = run.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_its_files(workload):
    spec = run.resolve(BENCH, workload)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert hasattr(spec["driver"], "Cell")
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert m["moves"] in names
        assert callable(spec["readers"][m["name"]])


def test_metric_and_cell_lists_name_real_entries():
    cells = set(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    configs = {c["name"] for c in BENCH["configs"]}
    assert {c["config"] for c in BENCH["workloads"]} == configs


def copy_checkout(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    root = copy_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/g500-bfs-kron20.json").read_text())
    cfg.update(name="g500-bfs-kron18", scale=18)
    (root / "bench/configs/g500-bfs-kron18.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/roots.json").read_text())
    mix.update(warmup=8)
    (root / "bench/traffic/roots-warm8.json").write_text(json.dumps(mix))
    (root / "bench/metrics/edges_per_traversal.py").write_text(
        "def read(ctx):\n    c = ctx['counters']\n"
        "    return c['edges'] / c['traversals']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="g500-bfs-kron18",
                                 file="bench/configs/g500-bfs-kron18.json"))
    bench["end_to_end"][0]["workloads"] += ["g500-bfs-kron18.roots",
                                            "g500-bfs-kron20.roots-warm8"]
    bench["workloads"] += [
        {"name": "g500-bfs-kron18.roots", "config": "g500-bfs-kron18",
         "traffic": "roots", "chips": 1, "why": "a new cell"},
        {"name": "g500-bfs-kron20.roots-warm8", "config": "g500-bfs-kron20",
         "traffic": "roots-warm8", "chips": 1, "why": "a new mix"}]
    bench["per_layer"].append(
        {"name": "edges_per_traversal", "unit": "edges", "better": "higher",
         "source": "program_counter", "layer": "host dispatch",
         "moves": "teps.bfs", "workloads": ["g500-bfs-kron18.roots"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = run.resolve(run.load_benchmark(str(root)), "g500-bfs-kron18.roots",
                       str(root))
    assert spec["config"]["scale"] == 18
    assert spec["readers"]["edges_per_traversal"](
        {"counters": {"edges": 10, "traversals": 2}}) == 5
    spec = run.resolve(run.load_benchmark(str(root)),
                       "g500-bfs-kron20.roots-warm8", str(root))
    assert spec["mix"]["warmup"] == 8
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(run.ROOT, "bench",
                                                       "traffic")))


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_seeded(name):
    with open(os.path.join(run.ROOT, "bench", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    degrees = np.random.default_rng(0).integers(0, 5, 1000)
    a = trafficgen.generate(mix, 2 ** 40 + 3, degrees)
    b = trafficgen.generate(mix, 2 ** 40 + 3, degrees)
    c = trafficgen.generate(mix, 3, degrees)
    assert a == b and a != c
    assert len(a) == int(mix["max_requests"])
    assert all(degrees[r] > 0 for r in a)


def test_unknown_traffic_is_refused():
    degrees = np.ones(10, int)
    for bad in ({"arrivals": "open_loop"}, {"sources": "zipf"}):
        mix = dict({"arrivals": "back_to_back", "sources": "uniform",
                    "max_requests": 4}, **bad)
        with pytest.raises(ValueError, match="unknown"):
            trafficgen.generate(mix, 1, degrees)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
