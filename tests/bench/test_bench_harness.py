"""The harness: cells resolve by name, the command path reaches its
last line, it refuses to run off the TPU, and a new cell needs only new
files and an entry."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run
from specs import load_benchmark, resolve_cell, sweep_points

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = load_benchmark()


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def test_benchmark_file_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = resolve_cell(cell)
    conf = {x["name"]: x for x in BENCH["configs"]}[c["config"]["name"]]
    assert conf["file"].startswith("bench/")
    assert c["chips"] in (1, 4)
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(run.load_reader(m["name"]))
    assert c["end_to_end"] and c["per_layer"]
    pts = sweep_points(c["config"], c["traffic"], 2 ** 33 + 1, 0, 0)
    assert pts and all(p.config is c["config"] for p in pts)


def test_seeds_are_reproducible_and_fresh_per_sweep():
    c = resolve_cell(BENCH["workloads"][0]["name"])
    a = sweep_points(c["config"], c["traffic"], 2 ** 32 + 9, 0, 4)
    b = sweep_points(c["config"], c["traffic"], 2 ** 32 + 9, 0, 4)
    d = sweep_points(c["config"], c["traffic"], 2 ** 32 + 9, 0, 5)
    w = sweep_points(c["config"], c["traffic"], 2 ** 32 + 9, 1, 4)
    seeds = {(p.sim_seed, p.workload_seed) for p in a}
    assert seeds == {(p.sim_seed, p.workload_seed) for p in b}
    assert not seeds & {(p.sim_seed, p.workload_seed) for p in d + w}


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_reaches_its_last_line(tiny_cell, bench_env, capsys,
                                        trace):
    cell = tiny_cell("giga4096.storage_kills_ecmp")
    rc = run.main(["--workload", cell["name"], "--seed", str(2 ** 33 + 3),
                   "--seconds", "0.5", "--trace", str(trace)],
                  require_tpu=False, cell=cell)
    out = capsys.readouterr()
    assert rc == 0
    res = _last_json(out.out)
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1 and "kind" in res["device"]
    assert res["attempted"] > 0
    if trace:
        assert "window_compiles" in res["metrics"]
        assert "busy_s" in res["device"]
    else:
        assert set(res["metrics"]) == {"flow_slots_per_s", "setup_s"}
        assert res["metrics"]["flow_slots_per_s"]["value"] > 0
    # the numbers compared, each beside its limit, end standard error
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_run_off_the_tpu_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs" in p.stderr


def test_a_new_cell_needs_only_new_files_and_an_entry(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: (tmp_path / p).read_bytes()
              for p in map(str, (tmp_path / "bench").rglob("*"))
              if os.path.isfile(p)}
    cfg = json.loads((tmp_path / "bench/configs/testbed64.json").read_text())
    cfg.update(name="testbed64_2planes")
    cfg["topology"]["n_planes"] = 2
    (tmp_path / "bench/configs/testbed64_2planes.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/incast_ecmp.json").write_text(json.dumps(
        {"routing": ["ecmp"], "nic": ["spx"], "fault_frac": [0.5],
         "kills": [None], "seeds_per_sweep": 3}))
    bench["configs"].append({"name": "testbed64_2planes",
                             "source": "https://example.org",
                             "file": "bench/configs/testbed64_2planes.json",
                             "reduced": [], "why": "two planes"})
    bench["workloads"].append({"name": "testbed64_2planes.incast_ecmp",
                               "config": "testbed64_2planes",
                               "traffic": "incast_ecmp", "chips": 1,
                               "why": "ecmp only"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = resolve_cell("testbed64_2planes.incast_ecmp", root=str(tmp_path))
    assert c["config"]["topology"]["n_planes"] == 2
    assert len(sweep_points(c["config"], c["traffic"], 5, 0, 0)) == 3
    assert [m["name"] for m in c["end_to_end"]] == \
        [m["name"] for m in BENCH["end_to_end"]]
    # nothing that was there changed
    for p, b in before.items():
        assert (tmp_path / p).read_bytes() == b
