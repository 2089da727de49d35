"""Where the JAX path puts things: its compilation cache, the platform of
NumPy pool workers, and a sweep's lanes on a device mesh.

An accelerator belongs to one process, so pool workers must never start
a backend of their own beside a parent that holds the chip; and a
compile cache found by the next process must sit at a fixed path.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.experiments import execute_points
from repro.experiments.execute import (DEFAULT_COMPILE_CACHE_DIR,
                                       _xla_backend_live,
                                       compile_cache_entries,
                                       enable_compile_cache)
from repro.scenarios import get_scenario

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env) -> dict:
    """Run `code` in a fresh interpreter (JAX state that a test process
    has already fixed, like the platform or the device count, starts
    over there) and parse the JSON on its last stdout line."""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
             **env})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_env_wins_over_argument(cache_config, monkeypatch,
                                              tmp_path):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = enable_compile_cache(str(tmp_path / "from_arg"))
    assert got == jax.config.jax_compilation_cache_dir == env_dir
    assert not (tmp_path / "from_arg").exists()
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_default_is_fixed_and_ignored(cache_config,
                                                    monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert os.path.abspath(DEFAULT_COMPILE_CACHE_DIR) == want
    assert enable_compile_cache() == jax.config.jax_compilation_cache_dir \
        == want
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


def test_jax_sweep_writes_its_cache_where_the_env_says(tmp_path):
    env_dir = tmp_path / "cache"
    res = _run(
        "import json\n"
        "from repro.experiments import execute_points\n"
        "from repro.scenarios import get_scenario\n"
        "spec = get_scenario('flap_during_incast').with_sim(slots=20)\n"
        "execute_points([spec], backend='jax',\n"
        "               compile_cache_dir='ignored_cache_dir')\n"
        "import jax\n"
        "print(json.dumps({'dir': jax.config.jax_compilation_cache_dir}))",
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(env_dir))
    assert res["dir"] == str(env_dir)
    assert compile_cache_entries(str(env_dir)) > 0
    assert not os.path.exists(os.path.join(ROOT, "ignored_cache_dir"))


def _worker_platform(spec, compiled, result):
    """`derive` hook run inside the pool worker."""
    import jax
    return {"platform": jax.default_backend(), "pid": os.getpid()}


def test_numpy_pool_workers_stay_on_cpu():
    spec = get_scenario("train_step_flap")
    points = [spec.with_sim(seed=s) for s in (0, 1)]
    ms = execute_points(points, backend="numpy", processes=2,
                        derive=_worker_platform)
    assert [m.extra["platform"] for m in ms] == ["cpu", "cpu"]
    assert os.getpid() not in {m.extra["pid"] for m in ms}
    assert jax.default_backend() == "cpu"


def test_pinned_workers_never_need_the_parents_platform():
    """With a platform JAX cannot start in the environment, only the
    pool initializer's CPU pin lets a worker answer at all — and a
    schedule compile in the parent starts no backend there."""
    res = _run(
        "import json, os\n"
        "from repro.experiments.execute import (_xla_backend_live,\n"
        "                                       execute_points)\n"
        "from repro.scenarios import compile_scenario, get_scenario\n"
        "def platform(spec, compiled, result):\n"
        "    import jax\n"
        "    return {'platform': jax.default_backend(),\n"
        "            'pid': os.getpid()}\n"
        "spec = get_scenario('train_step_flap')\n"
        "compile_scenario(spec)\n"
        "ms = execute_points([spec.with_sim(seed=s) for s in (0, 1)],\n"
        "                    backend='numpy', processes=2,\n"
        "                    derive=platform)\n"
        "import jax._src.xla_bridge as xb\n"
        "print(json.dumps({'platforms': [m.extra['platform'] for m in ms],\n"
        "                  'pids': [m.extra['pid'] for m in ms],\n"
        "                  'parent': os.getpid(),\n"
        "                  'parent_live': _xla_backend_live(),\n"
        "                  'parent_backends': len(xb._backends)}))",
        JAX_PLATFORMS="no_such_platform")
    assert res["platforms"] == ["cpu", "cpu"]
    assert res["parent"] not in res["pids"]
    assert not res["parent_live"] and res["parent_backends"] == 0


def test_backend_probe_sees_a_live_backend(monkeypatch):
    from repro.netsim.jx import engine
    monkeypatch.setattr(engine, "_BACKEND_USED", False)
    jnp.zeros(1).block_until_ready()
    assert _xla_backend_live()


def test_lane_mesh_matches_one_device():
    """A grid sharded over four (virtual) devices is one launch, and
    every lane reproduces the single-device run bit for bit."""
    res = _run(
        "import json\n"
        "import numpy as np, jax\n"
        "from repro.netsim.jx import dispatch_stats, reset_dispatch_stats\n"
        "from repro.netsim.jx.megabatch import run_megabatch\n"
        "from repro.scenarios import compile_scenario, get_scenario\n"
        "base = get_scenario('flap_during_incast').with_sim(slots=60)\n"
        "pts = [compile_scenario(base.with_sim(routing=r, nic=n, seed=s))\n"
        "       for r in ('ar', 'ecmp') for n in ('spx', 'dcqcn')\n"
        "       for s in (0, 1, 2)]\n"
        "reset_dispatch_stats()\n"
        "four = run_megabatch(pts)\n"
        "launches = dispatch_stats()['dispatches']\n"
        "one = run_megabatch(pts, n_devices=1)\n"
        "same = all(np.array_equal(getattr(a, f), getattr(b, f))\n"
        "           for a, b in zip(four, one)\n"
        "           for f in ('mean_goodput', 'completion_slot',\n"
        "                     'total_goodput', 'util_up_last'))\n"
        "print(json.dumps({'devices': len(jax.devices()),\n"
        "                  'launches': launches, 'same': same}))",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert res == {"devices": 4, "launches": 1, "same": True}
