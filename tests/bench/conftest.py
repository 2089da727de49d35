"""Shared helpers of the benchmark's tests: `bench/` and `src/` on the
path, and tiny versions of the cells that a CPU test run can hold."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(cell):
    """The same cell at a size a CPU test can hold: the testbed grid
    over 120 slots and fewer axes, the giga fabric cut to 128 hosts
    (large enough that a float32 fork after the kills stays local, so
    the sound run tracks the reference) and half the kills."""
    cell = copy.deepcopy(cell)
    c, t = cell["config"], cell["traffic"]
    if c["name"] == "testbed64":
        c["sim"]["slots"] = 120
        t.update(fault_frac=[0.5],
                 seeds_per_sweep=min(2, t["seeds_per_sweep"]))
    else:
        c["topology"].update(n_leaves=32, n_spines=8, hosts_per_leaf=4)
        c["workload"]["fanout"] = 10
        c["sim"]["slots"] = 40
        c["faults"][0]["start_slot"] = 10
        t["kills"] = [k // 2 for k in t["kills"]]
    return cell


@pytest.fixture
def tiny_cell():
    from specs import resolve_cell

    return lambda name: shrink(resolve_cell(name))


@pytest.fixture
def tiny_mix():
    """A configuration under a traffic mix, cell or not, at test size."""
    from specs import load_mix

    def make(config, traffic):
        c, t = load_mix(config, traffic)
        return shrink({"config": c, "traffic": t})
    return make


@pytest.fixture
def bench_env(monkeypatch, tmp_path):
    """Run the harness in-process on the CPU, with its compile cache in
    a temporary directory, restoring what it sets."""
    import jax

    import run

    monkeypatch.setattr(run, "CACHE_DIR", str(tmp_path / "jax_cache"))
    # `run.main` points JAX_COMPILATION_CACHE_DIR at CACHE_DIR; this
    # puts the variable back as it was once the test ends
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    x64 = bool(jax.config.jax_enable_x64)
    yield
    jax.config.update("jax_enable_x64", x64)
