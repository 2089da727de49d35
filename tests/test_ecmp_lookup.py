"""ECMP link lookups by one-hot contraction against the point gathers
they replace on a TPU (`engine.ecmp_onehot`): the same values bit for
bit, table by table and through whole launches, and the gathers kept
where the platform or the shapes call for them."""
from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.netsim.fabric import ECN_QUEUE_THRESH, Q_CAP
from repro.netsim.jx import engine, megabatch
from repro.netsim.jx.state import FlowBatch
from repro.scenarios import compile_scenario, get_scenario
from repro.scenarios.spec import ReactionSpec

FORCED = 1 << 30                # one-hot bytes that every test shape fits


def _bits(a):
    a = np.asarray(a)
    return a.view({4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _tables(rng, shape, dtype):
    """Link tables as the engine holds them: fractions in [0, 1], queues
    up to `Q_CAP`, with exact zeros and ones, values on either side of
    the ECN threshold, `Q_CAP` itself and values with all mantissa
    bits set."""
    t = (rng.random(shape) * Q_CAP).astype(dtype)
    flat = t.reshape(-1)
    thresh = dtype(ECN_QUEUE_THRESH)
    special = [0.0, 1.0, Q_CAP, thresh, np.nextafter(thresh, dtype(0)),
               np.nextafter(thresh, dtype(Q_CAP)), dtype(1) / dtype(3),
               np.nextafter(dtype(1), dtype(0)),
               np.nextafter(dtype(2.0) ** -90, dtype(1))]
    for i, v in enumerate(special):
        flat[i::len(special) + 3] = v
    return jnp.asarray(t)


@pytest.mark.parametrize("x64", [False, True], ids=["float32", "float64"])
def test_contraction_equals_point_gathers(x64):
    P, L, S, F = 2, 8, 4, 96
    rng = np.random.default_rng(7)
    with jax.enable_x64(x64):
        dtype = np.float64 if x64 else np.float32
        ups = tuple(_tables(rng, (P, L, S), dtype) for _ in range(3))
        downs = tuple(_tables(rng, (P, S, L), dtype) for _ in range(3))
        src = rng.integers(0, L, F)
        dst = rng.integers(0, L, F)
        # pad flows: leaf 0 at both ends (`same_leaf`)
        src[-16:] = dst[-16:] = 0
        fb = FlowBatch(*[None] * 9)._replace(src_leaf=jnp.asarray(src),
                                              dst_leaf=jnp.asarray(dst))
        assign = jnp.asarray(rng.integers(0, S, (F, P)))
        onehots = engine._leaf_onehots(SimpleNamespace(n_leaves=L), fb)
        assert all(o.dtype == jnp.bfloat16 for o in onehots)

        def look(oh):
            return jax.jit(lambda a: engine._link_values(
                fb, a, ups, downs, oh))(assign)
        (gu, gd), (cu, cd) = look(None), look(onehots)
        for g, c in zip(gu + gd, cu + cd):
            assert c.dtype == g.dtype == dtype and c.shape == (F, P)
            np.testing.assert_array_equal(_bits(c), _bits(g))


def _giga_tiny(react):
    """`giga_fabric_storage`'s structure (2 planes, storage fanout,
    random fabric kills) on 32 hosts, ECMP and AR points."""
    base = get_scenario("giga_fabric_storage")
    w = replace(base.workloads[0], fanout=5)
    f = replace(base.faults[0], start_slot=6, count=2)
    spec = replace(base, topo=replace(base.topo, n_leaves=8, n_spines=4,
                                      hosts_per_leaf=4),
                   workloads=(w,), faults=(f,)).with_sim(
        slots=16, backend="jax", routing="ecmp")
    if react:
        spec = replace(spec, reaction=ReactionSpec(detect_slots=2,
                                                   mode="backup"))
    return [spec, spec.with_sim(seed=5), spec.with_sim(routing="ar")]


@pytest.fixture
def lookups(monkeypatch):
    """How many one-hot lookups the programs traced in the test hold."""
    calls = []
    real = engine._onehot_lookup

    def spy(*a):
        calls.append(1)
        return real(*a)
    monkeypatch.setattr(engine, "_onehot_lookup", spy)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    return calls


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("react", [False, True], ids=["plain", "react"])
@pytest.mark.parametrize("dispatch", ["lane_sorted", "switch"])
def test_megabatch_launch_with_the_contraction_equals_the_gathers(
        lookups, react, dispatch):
    caches, (group,) = megabatch.plan_megabatch(
        [compile_scenario(p) for p in _giga_tiny(react)])
    (cfg, pts), = megabatch._sub_groups(group, caches)
    shards, lanes, args, _ = megabatch._assemble_group(cfg, pts, caches, 1)
    assert cfg.react == react and len(lanes) == 2
    lanes = lanes if dispatch == "lane_sorted" else None

    def launch(budget):
        fn = engine._jitted_mb(replace(cfg, ecmp_onehot_bytes=budget),
                               shards, lanes)
        return [np.asarray(o) for o in fn(*args)]
    gathered = launch(0)
    assert not lookups
    contracted = launch(FORCED)
    assert lookups
    assert len(contracted) == len(gathered) == 4 + react
    for c, g in zip(contracted, gathered):
        np.testing.assert_array_equal(_bits(c), _bits(g))


def test_single_point_run_with_the_contraction_equals_the_gathers(
        lookups, monkeypatch):
    """One point alone (`run_compiled`, a one-lane megabatch) under x64,
    the contraction chosen by the platform rule as a TPU would choose
    it."""
    spec = _giga_tiny(react=False)[0]
    with jax.enable_x64(True):
        gathered = engine.run_compiled(compile_scenario(spec))
        assert not lookups
        monkeypatch.setattr(engine, "ecmp_onehot_budget",
                            lambda platform: FORCED)
        monkeypatch.setattr(engine, "_JIT_CACHE", {})
        contracted = engine.run_compiled(compile_scenario(spec))
        assert lookups
    for f in ("mean_goodput", "completion_slot", "total_goodput",
              "util_up_last"):
        np.testing.assert_array_equal(_bits(getattr(contracted, f)),
                                      _bits(getattr(gathered, f)))


def test_cpu_keeps_the_gathers(lookups):
    from repro.experiments import execute_points

    points = _giga_tiny(react=False)
    compiled = compile_scenario(points[0])
    cfg = engine.JxConfig.from_sim(compiled.cfg, points[0].topo)
    assert cfg.ecmp_onehot_bytes == 0
    assert not engine.ecmp_onehot(cfg, len(compiled.flows))
    execute_points(points)
    assert not lookups


def test_platform_and_shapes_pick_the_lookup():
    spec = get_scenario("giga_fabric_storage")
    cfg = engine.JxConfig.from_sim(compile_scenario(
        spec.with_sim(slots=1)).cfg, spec.topo)
    tpu = replace(cfg, ecmp_onehot_bytes=engine.ecmp_onehot_budget("tpu"))
    assert engine.ecmp_onehot_budget("cpu") == 0
    # the giga cell's flow bucket on 256 leaves: 64 MB a one-hot
    assert tpu.n_leaves == 256 and engine.ecmp_onehot(tpu, 131072)
    # beyond the guard (256 MB a one-hot) the gathers stay
    assert not engine.ecmp_onehot(tpu, 1 << 19)
    # fat-tree fabrics and the chunked flow axis keep their gathers
    assert not engine.ecmp_onehot(replace(tpu, kind="fat_tree"), 131072)
    assert not engine.ecmp_onehot(replace(tpu, flow_chunk=4096), 131072)
