"""Sparse link-load and host-port sums by contraction of the leaf
one-hots (`engine.onehot_agg`) against the scatter-adds they replace on
a TPU: the same buckets bit for bit where the sums are exact in
float32, within rounding elsewhere, the same launches to float32
tolerance, and the scatter-adds kept where the platform, the precision
or the shapes call for them."""
from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import execute_points
from repro.netsim.jx import engine, megabatch
from repro.netsim.jx.megabatch import FLOW_BUCKET_MIN, _bucket
from repro.netsim.jx.state import FlowBatch
from repro.scenarios import compile_scenario, get_scenario

FORCED = 1 << 30                # one-hot bytes that every test shape fits
P, L, S, HPL = 2, 8, 4, 4
H, F, PAD = L * HPL, 96, 16


def _bits(a):
    a = np.asarray(a)
    return a.view({4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _cfg():
    return SimpleNamespace(n_planes=P, n_leaves=L, n_spines=S, n_hosts=H,
                           kind="leaf_spine", agg_mode="sparse",
                           flow_chunk=0, ecmp_onehot_bytes=FORCED)


def _flows(values):
    """A flow batch, its ECMP spines and its (F, P) rates.  The hosts of
    the last leaf send nothing, so its buckets stay empty; a quarter of
    the flows stay on their leaf; the last `PAD` flows are pads (host 0
    to host 0, rate +0.0).  `values`: "dyadic" rates (multiples of 1/64
    below 4, whose sums are exact in float32) or "random" ones."""
    rng = np.random.default_rng(11)
    src = rng.integers(0, H - HPL, F)
    dst = rng.integers(0, H, F)
    local = rng.random(F) < 0.25
    dst[local] = src[local] // HPL * HPL + rng.integers(0, HPL, local.sum())
    src[-PAD:] = dst[-PAD:] = 0
    if values == "dyadic":
        rate = rng.integers(1, 256, (F, P)) / np.float32(64)
    else:
        rate = rng.random((F, P))
    rate = rate.astype(np.float32)
    rate[-PAD:] = 0.0
    fb = FlowBatch(*[None] * 9)._replace(
        src=jnp.asarray(src), dst=jnp.asarray(dst),
        src_leaf=jnp.asarray(src // HPL), dst_leaf=jnp.asarray(dst // HPL),
        same_leaf=jnp.asarray(src // HPL == dst // HPL))
    return fb, jnp.asarray(rng.integers(0, S, (F, P))), jnp.asarray(rate)


def _sums(which, fb, assign, rate, onehots):
    """The engine's sparse sums of `which` kind: ECMP link loads of the
    rates off the leaf (as `_route_ecmp` takes them), or the host-port
    loads of every flow's rate at both ends (as `_slot_step` takes
    them)."""
    cfg = _cfg()
    if which == "link":
        fabric = jnp.where(fb.same_leaf[:, None], 0.0, rate)
        return engine._ecmp_link_loads(cfg, fb, assign, fabric, onehots)
    src, dst = onehots if onehots is not None else (None, None)
    return (engine._host_sum(cfg, rate, fb.src, None, src),
            engine._host_sum(cfg, rate, fb.dst, None, dst))


@pytest.mark.parametrize("values", ["dyadic", "random"])
@pytest.mark.parametrize("which", ["link", "host"])
def test_contraction_equals_the_scatter(which, values):
    fb, assign, rate = _flows(values)
    onehots = engine._leaf_onehots(_cfg(), fb)
    scattered = jax.jit(lambda r: _sums(which, fb, assign, r, None))(rate)
    contracted = jax.jit(lambda r: _sums(which, fb, assign, r,
                                         onehots))(rate)
    empty = 0
    for s, c in zip(scattered, contracted):
        s, c = np.asarray(s), np.asarray(c)
        assert c.dtype == s.dtype == np.float32 and c.shape == s.shape
        empty += np.count_nonzero(s == 0)
        if values == "dyadic":
            np.testing.assert_array_equal(_bits(c), _bits(s))
        else:
            # empty buckets read +0.0 on both sides, bit for bit
            np.testing.assert_array_equal(_bits(c[s == 0]), _bits(s[s == 0]))
            assert np.all(np.abs(c - s) <= 4 * np.spacing(np.abs(s)))
    # the silent leaf's uplinks or sending ports
    assert empty >= P * (S if which == "link" else HPL)


def _giga_tiny(monkeypatch):
    """`giga_fabric_storage`'s structure on 32 hosts with sparse
    aggregation: two ECMP points and one weighted-AR point.  Demand 0.27
    a flow: at the registry's 0.3 each plane carries 0.15 a flow, and
    sums of 0.15 put a queue exactly on the RTT trim threshold (2.2
    slots) by the fifth slot, where any change of summation order forks
    the float32 trajectory (see PERF.md, section 7)."""
    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    base = get_scenario("giga_fabric_storage")
    w = replace(base.workloads[0], fanout=5, demand=0.27)
    f = replace(base.faults[0], start_slot=6, count=2)
    spec = replace(base, topo=replace(base.topo, n_leaves=8, n_spines=4,
                                      hosts_per_leaf=4),
                   workloads=(w,), faults=(f,)).with_sim(
        slots=16, backend="jax", routing="ecmp")
    return [spec, spec.with_sim(seed=5), spec.with_sim(routing="war")]


@pytest.fixture
def leaf_sums(monkeypatch):
    """How many `_leaf_sum` contractions the programs traced in the test
    hold."""
    calls = []
    real = engine._leaf_sum

    def spy(*a):
        calls.append(1)
        return real(*a)
    monkeypatch.setattr(engine, "_leaf_sum", spy)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    return calls


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("dispatch", ["lane_sorted", "switch"])
def test_megabatch_launch_with_the_contraction_equals_the_scatter(
        leaf_sums, monkeypatch, dispatch):
    caches, (group,) = megabatch.plan_megabatch(
        [compile_scenario(p) for p in _giga_tiny(monkeypatch)])
    (cfg, pts), = megabatch._sub_groups(group, caches)
    shards, lanes, args, _ = megabatch._assemble_group(cfg, pts, caches, 1)
    assert cfg.agg_mode == "sparse" and len(lanes) == 2
    lanes = lanes if dispatch == "lane_sorted" else None

    def launch(budget):
        fn = engine._jitted_mb(replace(cfg, ecmp_onehot_bytes=budget),
                               shards, lanes)
        return [np.asarray(o) for o in fn(*args)]
    scattered = launch(0)
    assert not leaf_sums
    contracted = launch(FORCED)
    # lane-sorted: two link-load and two host-port sums in the ECMP
    # lane, two host-port sums in the WAR lane; under the switch, one
    # trace of each routing branch and one of the host-port sums
    assert len(leaf_sums) == (6 if lanes else 4)
    assert len(contracted) == len(scattered) == 4
    for c, s in zip(contracted, scattered):
        np.testing.assert_allclose(c, s, rtol=1e-5, atol=1e-6)


def test_platform_and_shapes_pick_the_sums():
    spec = get_scenario("giga_fabric_storage")
    cfg = replace(engine.JxConfig.from_sim(compile_scenario(
        spec.with_sim(slots=1)).cfg, spec.topo),
        ecmp_onehot_bytes=engine.ecmp_onehot_budget("tpu"))
    assert cfg.agg_mode == "sparse"
    # the giga cells' flow bucket on 256 leaves
    assert engine.onehot_agg(cfg, 131072)
    assert not engine.onehot_agg(replace(cfg, ecmp_onehot_bytes=0), 131072)
    # beyond the one-hots' guard, fat-tree fabrics, the chunked flow
    # axis and dense aggregation keep their scatter-adds or plans
    assert not engine.onehot_agg(cfg, 1 << 19)
    assert not engine.onehot_agg(replace(cfg, kind="fat_tree"), 131072)
    assert not engine.onehot_agg(replace(cfg, flow_chunk=4096), 131072)
    assert not engine.onehot_agg(replace(cfg, agg_mode="dense"), 131072)
    with jax.enable_x64(True):
        assert not engine.onehot_agg(cfg, 131072)


def test_cpu_keeps_the_scatter(leaf_sums, monkeypatch):
    points = _giga_tiny(monkeypatch)
    compiled = compile_scenario(points[0])
    cfg = engine.JxConfig.from_sim(compiled.cfg, points[0].topo)
    assert cfg.agg_mode == "sparse" and cfg.ecmp_onehot_bytes == 0
    assert not engine.onehot_agg(cfg, len(compiled.flows))
    fl = {}
    execute_points(points, flight=fl)
    assert not leaf_sums
    assert fl["counters"]["onehot_agg_flow_slots"] == 0


def test_x64_keeps_the_scatter(leaf_sums, monkeypatch):
    """A float64 run keeps the flow-ordered scatter-adds that match the
    NumPy engine bit for bit, even where the one-hots look its links
    up."""
    lookups = []
    real = engine._onehot_lookup
    monkeypatch.setattr(engine, "_onehot_lookup",
                        lambda *a: lookups.append(1) or real(*a))
    monkeypatch.setattr(engine, "ecmp_onehot_budget",
                        lambda platform: FORCED)
    with jax.enable_x64(True):
        engine.run_compiled(compile_scenario(_giga_tiny(monkeypatch)[0]))
    assert lookups and not leaf_sums


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_flow_chunk_keeps_the_scatter(leaf_sums, monkeypatch):
    monkeypatch.setenv("REPRO_JX_FLOW_CHUNK", "32")
    monkeypatch.setattr(engine, "ecmp_onehot_budget",
                        lambda platform: FORCED)
    fl = {}
    execute_points(_giga_tiny(monkeypatch), flight=fl)
    assert not leaf_sums
    assert fl["counters"]["onehot_agg_flow_slots"] == 0


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_fat_tree_keeps_the_scatter(leaf_sums, monkeypatch):
    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    monkeypatch.setattr(engine, "ecmp_onehot_budget",
                        lambda platform: FORCED)
    spec = get_scenario("ft_core_failure_resiliency").with_sim(
        slots=10, backend="jax")
    fl = {}
    execute_points([spec, spec.with_sim(routing="ecmp")], flight=fl)
    assert not leaf_sums
    assert fl["counters"]["onehot_agg_flow_slots"] == 0


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_onehot_agg_counter_counts_every_contracted_row(leaf_sums,
                                                        monkeypatch):
    """`onehot_agg_flow_slots` counts the launched flow-slots of every row
    whose host-port sums contract, ECMP and WAR alike, while
    `ecmp_onehot_flow_slots` still counts the ECMP rows alone."""
    monkeypatch.setattr(engine, "ecmp_onehot_budget",
                        lambda platform: FORCED)
    points = _giga_tiny(monkeypatch)
    F_b = _bucket(len(compile_scenario(points[0]).flows), FLOW_BUCKET_MIN)
    fl = {}
    execute_points(points, flight=fl)
    c = fl["counters"]
    assert leaf_sums
    assert c["onehot_agg_flow_slots"] == c["flow_slots_launched"] \
        == 16 * F_b * 3
    assert c["ecmp_onehot_flow_slots"] == 16 * F_b * 2
