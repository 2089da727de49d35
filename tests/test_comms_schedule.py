"""Collective-schedule co-simulation (`repro.comms`).

Pins the schedule compiler end to end:

  * golden phase tables for one dense (llama3-8b, dp4/pp2) and one MoE
    (phi3.5-moe, dp4/tp2) plan — window widths, phase counts, per-phase
    byte totals and flow counts;
  * hypothesis property: total scheduled (closed-transfer) bytes are
    invariant under the fabric plane count and under permutations of the
    tenant host order (DP-peer relabeling);
  * the flap resiliency signature: a plane flap during the DP sync
    window inflates the derived step time by a pinned margin and the
    post-heal step recovers within a pinned budget — on both backends;
  * megabatch: a seed grid over one schedule scenario is ONE dispatch
    and ONE compile;
  * satellite regressions: `workloads.all2all` emits the full ordered
    pair set (the historical dead-loop produced none), the analytic CCT
    helpers match their closed forms, and `stream_report` is
    dtype-aware with a 4-byte fallback for shape-only leaves.
"""
import math
import types

import jax
import numpy as np
import pytest

from repro.comms import plan_schedule, sim_bytes
from repro.comms.lower import lower_schedule
from repro.core.collectives import stream_report
from repro.core.planes import PlaneConfig
from repro.netsim.topology import LeafSpine
from repro.netsim.workloads import (all2all, all2all_cct_us,
                                    bus_bandwidth_gbps,
                                    ring_collective_cct_us)
from repro.scenarios import (ScenarioSpec, SimSpec, TenantSpec,
                             TopologySpec, WorkloadSpec, compile_scenario,
                             get_scenario)
from repro.scenarios.spec import ScheduleSpec

TOL = 1e-5


def _steps(c, res):
    """Derived per-step completion times for the first schedule."""
    sched = c.schedules[0]
    return sched.step_times(np.asarray(res.completion_slot),
                            c.spec.sim.slots)


# ---------------------------------------------------------------------------
# golden phase tables (dense + MoE)
# ---------------------------------------------------------------------------

def test_dense_plan_windows_and_phase_table():
    c = compile_scenario(get_scenario("train_step_baseline"))
    assert len(c.schedules) == 1
    s = c.schedules[0]
    assert (s.model, s.dp, s.tp, s.pp, s.n_ranks) == (
        "llama3-8b", 4, 1, 2, 8)
    # Window skeleton pinned: any byte-accounting drift lands here.
    assert (s.w_fwd, s.w_bwd, s.w_sync, s.pad) == (11, 22, 28, 2)
    assert s.step_period == 63
    assert s.step_starts == (0, 63, 126)
    # 3 steps x (fwd, bwd, sync) + one ckpt after step 2 (ckpt_every=2)
    names = [(p.name, p.step) for p in s.phases]
    assert names == [("fwd", 0), ("bwd", 0), ("sync", 0),
                     ("fwd", 1), ("bwd", 1), ("sync", 1), ("ckpt", 1),
                     ("fwd", 2), ("bwd", 2), ("sync", 2)]
    by = {(p.name, p.step): p for p in s.phases}
    # dense model: no a2a bytes in the fwd phase
    assert by[("fwd", 0)].n_flows == 0
    assert by[("fwd", 0)].sim_bytes == 0.0
    # DP sync: one ring stream per rank, 2(D-1)/D of the grad shard
    ar = sim_bytes(2.0 * 3 / 4 * (s.grad_bytes_real / 2), 1.0, 100.0)
    sync = by[("sync", 0)]
    assert sync.n_flows == 8
    assert sync.sim_bytes == pytest.approx(8 * ar)
    assert sync.start_slot == 33 and sync.stop_slot == 61
    # step-1 sync window [96, 124) is what the registry flap targets
    assert by[("sync", 1)].start_slot == 96
    assert by[("sync", 1)].stop_slot == 124
    ck = by[("ckpt", 1)]
    assert ck.n_flows == 8
    assert ck.sim_bytes == pytest.approx(
        8 * sim_bytes(s.grad_bytes_real / 2, 1.0, 100.0))
    # every step's completion set is the 8 sync streams (ckpt excluded)
    assert all(len(ix) == 8 for ix in s.step_flows)


def test_moe_plan_windows_and_phase_table():
    c = compile_scenario(get_scenario("train_step_flap_moe"))
    s = c.schedules[0]
    assert (s.model, s.dp, s.tp, s.pp, s.n_ranks) == (
        "phi3.5-moe-42b-a6.6b", 4, 2, 1, 8)
    assert (s.w_fwd, s.w_bwd, s.w_sync, s.pad) == (27, 54, 40, 2)
    assert s.step_period == 123
    assert s.step_starts == (0, 123, 246)
    assert [p.name for p in s.phases] == ["fwd", "bwd", "sync"] * 3
    by = {(p.name, p.step): p for p in s.phases}
    # EP all2all: ordered pairs within each DP group, per TP member
    fwd = by[("fwd", 0)]
    assert fwd.n_flows == 2 * 4 * 3            # tp * dp * (dp-1)
    assert fwd.sim_bytes > 0
    # total = per-rank a2a volume x all 8 ranks
    assert fwd.sim_bytes == pytest.approx(
        8 * sim_bytes(s.a2a_bytes_real, 1.0, 100.0))
    assert by[("sync", 1)].start_slot == 204   # registry flap window
    assert by[("sync", 1)].stop_slot == 244
    # completion set: 24 a2a exchanges + 8 sync streams per step
    assert all(len(ix) == 32 for ix in s.step_flows)


def test_schedule_plan_rejects_short_horizon():
    ss = ScheduleSpec(model="llama3-8b", dp=4, pp=2, line_rate_gbps=1.0)
    with pytest.raises(ValueError, match="slots"):
        plan_schedule(ss, slot_us=100.0, slots=10)


def test_schedule_spec_validation():
    with pytest.raises(ValueError, match="dp >= 2"):
        ScheduleSpec(dp=1).validate("x")
    topo = TopologySpec(n_leaves=2, n_spines=2, hosts_per_leaf=2)
    with pytest.raises(ValueError):        # 8 ranks > 4 hosts
        ScenarioSpec(
            name="x", topo=topo,
            workloads=(WorkloadSpec("schedule",
                                    schedule=ScheduleSpec(dp=8)),),
            sim=SimSpec(slots=400)).validate()
    with pytest.raises(ValueError, match="schedule"):
        ScenarioSpec(
            name="x", topo=topo,
            workloads=(WorkloadSpec("allreduce",
                                    schedule=ScheduleSpec()),),
            sim=SimSpec(slots=40)).validate()
    with pytest.raises(ValueError, match="schedule"):
        ScenarioSpec(
            name="x", topo=topo,
            workloads=(WorkloadSpec("schedule"),),
            sim=SimSpec(slots=40)).validate()


def test_phase_mult_lane_layout():
    """Lane 0 is always-on; fwd/bwd lanes tile the compute windows and
    never overlap; the compute lane is their union."""
    c = compile_scenario(get_scenario("train_step_baseline"))
    pm = c.phase_mult
    s = c.schedules[0]
    assert pm.shape == (c.spec.sim.slots, 4)
    assert (pm[:, 0] == 1.0).all()
    assert not np.any((pm[:, 1] > 0) & (pm[:, 2] > 0))
    np.testing.assert_array_equal(pm[:, 3],
                                  np.maximum(pm[:, 1], pm[:, 2]))
    t0 = s.step_starts[1]
    assert (pm[t0:t0 + s.w_fwd, 1] == 1.0).all()
    assert (pm[t0 + s.w_fwd:t0 + s.w_fwd + s.w_bwd, 2] == 1.0).all()
    # sync + pad windows: no pulsed compute traffic
    assert (pm[t0 + s.w_fwd + s.w_bwd:t0 + s.step_period, 1:] == 0).all()


# ---------------------------------------------------------------------------
# property: scheduled bytes invariant under plane count / host relabeling
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container without hypothesis: the
    HAVE_HYPOTHESIS = False  # deterministic sweep below still runs

if HAVE_HYPOTHESIS:
    SCHED = st.builds(
        ScheduleSpec,
        model=st.sampled_from(["llama3-8b", "phi3.5-moe-42b-a6.6b"]),
        dp=st.integers(2, 4), tp=st.integers(1, 2), pp=st.integers(1, 2),
        steps=st.integers(1, 2), microbatches=st.sampled_from([2, 4]),
        tokens_per_rank=st.sampled_from([256, 512]),
        line_rate_gbps=st.just(1.0),
        ckpt_every=st.integers(0, 2))


def _closed_bytes(flows):
    return sorted(f.bytes_total for f in flows
                  if math.isfinite(f.bytes_total))


def _lower(ss, n_planes, hosts=None):
    topo = TopologySpec(n_leaves=4, n_spines=2, hosts_per_leaf=4,
                        n_planes=n_planes)
    plan = plan_schedule(ss, 100.0, 10 ** 9, n_planes=n_planes)
    sim = SimSpec(slots=ss.steps * plan.step_period, slot_us=100.0)
    w = WorkloadSpec("schedule", schedule=ss)
    if hosts is None:
        hosts = list(range(ss.n_ranks))
    return lower_schedule(w, hosts, topo, sim, "main")


def _check_bytes_invariant(ss, planes, seed):
    fl1, pm1, s1 = _lower(ss, n_planes=1)
    flp, pmp, sp = _lower(ss, n_planes=planes)
    # plane count changes gradient chunking, never total volume
    assert _closed_bytes(flp) == pytest.approx(_closed_bytes(fl1))
    assert sp.grad_bytes_real == pytest.approx(s1.grad_bytes_real)
    np.testing.assert_array_equal(pmp, pm1)
    # DP-peer relabeling (host permutation) preserves the byte multiset,
    # the flow count, and the phase table
    rng = np.random.default_rng(seed)
    perm = [int(h) for h in rng.permutation(ss.n_ranks)]
    flh, pmh, sh = _lower(ss, n_planes=1, hosts=perm)
    assert len(flh) == len(fl1)
    assert _closed_bytes(flh) == pytest.approx(_closed_bytes(fl1))
    assert sh.phases == s1.phases
    # phase table accounts exactly for the closed bytes scheduled
    assert sum(p.sim_bytes for p in s1.phases) == pytest.approx(
        sum(_closed_bytes(fl1)))
    assert sum(p.n_flows for p in s1.phases) == len(_closed_bytes(fl1))


if HAVE_HYPOTHESIS:
    @given(ss=SCHED, planes=st.integers(2, 8),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=15, deadline=None)
    def test_scheduled_bytes_invariant(ss, planes, seed):
        _check_bytes_invariant(ss, planes, seed)


@pytest.mark.parametrize("ss,planes,seed", [
    (ScheduleSpec(model="llama3-8b", dp=4, tp=1, pp=2, steps=2,
                  line_rate_gbps=1.0, tokens_per_rank=512,
                  ckpt_every=1), 4, 0),
    (ScheduleSpec(model="llama3-8b", dp=2, tp=2, pp=2, steps=1,
                  line_rate_gbps=1.0, tokens_per_rank=256), 8, 1),
    (ScheduleSpec(model="phi3.5-moe-42b-a6.6b", dp=4, tp=2, pp=1,
                  steps=2, line_rate_gbps=1.0,
                  tokens_per_rank=512), 3, 2),
    (ScheduleSpec(model="phi3.5-moe-42b-a6.6b", dp=3, tp=1, pp=2,
                  steps=1, line_rate_gbps=1.0, tokens_per_rank=256,
                  ckpt_every=1), 2, 3),
])
def test_scheduled_bytes_invariant_fixed(ss, planes, seed):
    """Deterministic anchor for the invariance property (always runs,
    even where hypothesis is unavailable)."""
    _check_bytes_invariant(ss, planes, seed)


# ---------------------------------------------------------------------------
# flap resiliency signature (numpy tier-1; jax parity below)
# ---------------------------------------------------------------------------

def test_baseline_steps_are_steady():
    c = compile_scenario(get_scenario("train_step_baseline"))
    stp = _steps(c, c.run(backend="numpy"))
    assert stp.shape == (3,)
    # uncongested: every step completes at the same offset
    assert np.ptp(stp) == 0.0
    assert stp[0] <= c.schedules[0].step_period


def test_flap_inflates_step_time_and_recovers():
    cb = compile_scenario(get_scenario("train_step_baseline"))
    base = _steps(cb, cb.run(backend="numpy"))
    cf = compile_scenario(get_scenario("train_step_flap"))
    flap = _steps(cf, cf.run(backend="numpy"))
    # step 0 is pre-fault: identical to baseline
    assert flap[0] == base[0]
    # the flap hits step 1's sync window: pinned inflation margin
    assert flap[1] / flap[0] >= 1.2
    # step 2 (post-heal) recovers within the pinned budget
    assert flap[2] / flap[0] <= 1.1


def test_flap_moe_signature():
    c = compile_scenario(get_scenario("train_step_flap_moe"))
    stp = _steps(c, c.run(backend="numpy"))
    assert stp[1] / stp[0] >= 1.2
    assert stp[2] / stp[0] <= 1.1


# ---------------------------------------------------------------------------
# backend parity + megabatch single-compile
# ---------------------------------------------------------------------------

def test_schedule_backend_parity():
    spec = get_scenario("train_step_flap")
    with jax.enable_x64(True):
        c = compile_scenario(spec)
        ref = c.run(backend="numpy")
        jres = c.run(backend="jax")
    np.testing.assert_array_equal(jres.completion_slot,
                                  ref.completion_slot)
    np.testing.assert_allclose(jres.mean_goodput, ref.mean_goodput,
                               atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(_steps(c, jres), _steps(c, ref))


def test_schedule_megabatch_single_compile():
    """A seed grid over one schedule scenario fuses into ONE dispatch
    and ONE compile — the phase timeline must not fragment buckets."""
    from repro.experiments.axes import Axis
    from repro.experiments.execute import execute_points
    from repro.experiments.experiment import Experiment
    from repro.netsim.jx import dispatch_stats, reset_dispatch_stats

    exp = Experiment(name="test_comms.smoke", base="train_step_flap",
                     axes=Axis("seed", (0, 1)))
    points = [p.spec for p in exp.points()]
    reset_dispatch_stats()
    rows = execute_points(points, backend="jax",
                          jx_dispatch="megabatch")
    stats = dispatch_stats()
    assert stats["dispatches"] == 1
    assert stats["compiles"] == 1
    assert len(rows) == 2


# ---------------------------------------------------------------------------
# satellite regressions: all2all builder, CCT helpers, stream_report
# ---------------------------------------------------------------------------

def test_all2all_emits_full_ordered_pair_set():
    """Regression for the dead loop that yielded zero flows."""
    t = LeafSpine(n_leaves=2, n_spines=2, hosts_per_leaf=4)
    hosts = list(range(6))
    flows = all2all(t, hosts, bytes_per_pair=7.0)
    assert len(flows) == 6 * 5
    assert {(f.src, f.dst) for f in flows} == {
        (a, b) for a in hosts for b in hosts if a != b}
    assert all(f.demand == pytest.approx(1.0 / 5) for f in flows)
    assert all(f.bytes_total == 7.0 for f in flows)


def test_all2all_cct_closed_form():
    # payload = (n-1)/n * msg; latency paid once per chunk round
    msg, n, bw, lat = 64e6, 8, 400.0, 10.0
    payload = msg * 7 / 8
    want = payload * 8.0 / (bw * 1e3) + math.ceil(
        payload / (4 << 20)) * lat
    assert all2all_cct_us(msg, n, bw, lat) == pytest.approx(want)
    # sub-chunk message still pays one latency round
    small = all2all_cct_us(1024.0, 4, bw, lat)
    assert small == pytest.approx(1024 * 0.75 * 8 / (bw * 1e3) + lat)


def test_ring_collective_cct_closed_form():
    msg, n, bw, lat = 64e6, 8, 400.0, 10.0
    step = (msg / n) * 8.0 / (bw * 1e3) + lat
    assert ring_collective_cct_us(msg, n, bw, lat) == pytest.approx(
        (n - 1) * step)
    # latency-dominated regime: doubling latency ~doubles CCT
    lo = ring_collective_cct_us(1.0, 8, 400.0, 10.0)
    hi = ring_collective_cct_us(1.0, 8, 400.0, 20.0)
    assert hi / lo == pytest.approx(2.0, rel=1e-3)


def test_bus_bandwidth_normalization():
    msg, n, bw, lat = 64e6, 8, 400.0, 0.0
    cct = all2all_cct_us(msg, n, bw, lat)
    # zero latency, algbw == busbw * n/(n-1) == line rate
    assert bus_bandwidth_gbps(msg, cct, n) == pytest.approx(bw)
    assert bus_bandwidth_gbps(msg, 0.0, n) > 0  # guarded denominator


def test_stream_report_is_dtype_aware():
    import jax.numpy as jnp
    cfg = PlaneConfig(n_planes=2, microchunks=2)
    f32 = {"w": jnp.zeros((64, 8), jnp.float32)}
    bf16 = {"w": jnp.zeros((64, 8), jnp.bfloat16)}
    b32 = stream_report(f32, cfg).chunk_bytes.sum()
    b16 = stream_report(bf16, cfg).chunk_bytes.sum()
    assert b32 == 64 * 8 * 4
    assert b16 == 64 * 8 * 2          # pre-fix: dtype ignored -> 4x8x64
    # shape-only leaves (no dtype attribute) fall back to 4 bytes/elem
    shell = [types.SimpleNamespace(shape=(16, 4))]
    assert stream_report(shell, cfg).chunk_bytes.sum() == 16 * 4 * 4
