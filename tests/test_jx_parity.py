"""NumPy-vs-JAX backend parity.

Every registry scenario compiles to a static fault timeline, so the JAX
backend must reproduce the NumPy trajectory.  With x64 enabled the two
engines agree within 1e-5 on mean goodput, completion slots, the total
goodput time series, and every distilled per-tenant metric — across
routings (ar | war | ecmp) and NIC stacks (spx | dcqcn).  Giga-scale
scenarios (>= 4096 hosts) are the one exception: there, cross-engine
summation-order ulps fork a bounded handful of host trajectories at
ECN thresholds, so parity is asserted as contained-fork + tight
aggregates instead (_assert_parity_chaotic).

Fast cross-product cases run in tier-1; the full-length all-registry
sweep and the batched-sweep equivalence run under `-m slow` (the CI
jax-backend job includes them).
"""
import jax
import numpy as np
import pytest

from repro.netsim.jx import compile_fault_timeline, has_static_timeline
from repro.scenarios import (SweepGrid, compile_scenario, distill_metrics,
                             get_scenario, list_scenarios, sweep)

TOL = 1e-5


def _run_both(spec):
    with jax.enable_x64(True):
        ref = compile_scenario(spec).run(backend="numpy")
        jres = compile_scenario(spec).run(backend="jax")
    return ref, jres


def _assert_parity(spec, ref, jres):
    np.testing.assert_allclose(jres.mean_goodput, ref.mean_goodput,
                               atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(jres.completion_slot,
                                  ref.completion_slot)
    np.testing.assert_allclose(jres.total_goodput, ref.total_goodput,
                               atol=TOL * len(ref.mean_goodput), rtol=TOL)
    np.testing.assert_allclose(jres.util_up_last, ref.util_up_last,
                               atol=TOL, rtol=TOL)
    assert jres.groups == ref.groups
    np.testing.assert_array_equal(jres.group_of, ref.group_of)
    # failure-reaction observables: both backends must expose the same
    # per-slot blackholed-byte series (or neither, when no reaction)
    bh_r = getattr(ref, "blackhole_timeline", None)
    bh_j = getattr(jres, "blackhole_timeline", None)
    assert (bh_r is None) == (bh_j is None)
    if bh_r is not None:
        np.testing.assert_allclose(bh_j, bh_r,
                                   atol=TOL * len(ref.mean_goodput),
                                   rtol=TOL)
    c = compile_scenario(spec)
    m_ref = distill_metrics(spec, c, ref)
    m_jx = distill_metrics(spec, c, jres)
    for t in m_ref.tenant_mean:
        assert m_jx.tenant_mean[t] == pytest.approx(m_ref.tenant_mean[t],
                                                    abs=TOL)
        assert m_jx.tenant_p01[t] == pytest.approx(m_ref.tenant_p01[t],
                                                   abs=TOL)
        assert m_jx.tenant_p99[t] == pytest.approx(m_ref.tenant_p99[t],
                                                   abs=TOL)
    assert m_jx.isolation_index == pytest.approx(m_ref.isolation_index,
                                                 abs=TOL)
    assert m_jx.recovery_slots == m_ref.recovery_slots
    assert m_jx.reaction_slots == m_ref.reaction_slots


def _assert_parity_chaotic(spec, ref, jres, fork_frac=0.05):
    """Parity for giga-scale scenarios (>= 4096 hosts), where exact
    per-host agreement is physically unattainable: the fluid queues
    integrate load, so the last-ulp summation-order difference between
    XLA reductions and numpy accumulation forks individual host
    trajectories at ECN thresholds.  With O(100k) flows some queue is
    always sitting on a threshold, so instead of 1e-5 everywhere we
    assert the fork stays *contained*: almost all hosts still agree at
    1e-5, forked hosts stay bounded, and every aggregate metric agrees
    tightly.  (Dense-vs-sparse aggregation and repeated jax runs remain
    bit-identical at this scale — see tests/test_sparse_agg.py — the
    spread here is strictly cross-engine.)"""
    r = np.asarray(ref.mean_goodput)
    j = np.asarray(jres.mean_goodput)
    forked = ~np.isclose(j, r, atol=TOL, rtol=TOL)
    assert forked.mean() <= fork_frac, (
        f"{forked.sum()}/{forked.size} hosts forked "
        f"({forked.mean():.2%} > {fork_frac:.0%})")
    assert np.abs(j - r).max() <= 0.05
    assert abs(j.mean() - r.mean()) <= 1e-3
    comp_diff = np.mean(jres.completion_slot != ref.completion_slot)
    assert comp_diff <= fork_frac
    np.testing.assert_allclose(jres.total_goodput, ref.total_goodput,
                               rtol=2e-2,
                               atol=1e-3 * len(r))
    # Instantaneous last-slot link utilization is the most fork-exposed
    # observable: one forked host's CC rate moves its whole link, so we
    # bound the spread (fraction + p99 + mean), not the worst link.
    util_diff = np.abs(np.asarray(jres.util_up_last)
                       - np.asarray(ref.util_up_last))
    assert (util_diff > TOL).mean() <= 3 * fork_frac
    assert np.quantile(util_diff, 0.99) <= 0.01
    assert util_diff.mean() <= 1e-3
    assert jres.groups == ref.groups
    np.testing.assert_array_equal(jres.group_of, ref.group_of)
    c = compile_scenario(spec)
    m_ref = distill_metrics(spec, c, ref)
    m_jx = distill_metrics(spec, c, jres)
    for t in m_ref.tenant_mean:
        assert m_jx.tenant_mean[t] == pytest.approx(m_ref.tenant_mean[t],
                                                    abs=1e-3)
        assert m_jx.tenant_p01[t] == pytest.approx(m_ref.tenant_p01[t],
                                                   abs=2e-2)
        assert m_jx.tenant_p99[t] == pytest.approx(m_ref.tenant_p99[t],
                                                   abs=2e-2)
    assert m_jx.isolation_index == pytest.approx(m_ref.isolation_index,
                                                 abs=1e-2)
    # recovery_slots: tuple of (start_slot, kind, slots_to_recover);
    # a forked trajectory may shift the recovery detection by a slot.
    assert len(m_jx.recovery_slots) == len(m_ref.recovery_slots)
    for (s_j, k_j, n_j), (s_r, k_r, n_r) in zip(m_jx.recovery_slots,
                                                m_ref.recovery_slots):
        assert (s_j, k_j) == (s_r, k_r)
        if n_j is None or n_r is None:
            assert n_j == n_r
        else:
            assert abs(n_j - n_r) <= 2


# ---------------------------------------------------------------------------
# tier-1: routing x nic cross on representative scenarios (reduced slots)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
@pytest.mark.parametrize("nic", ["spx", "dcqcn", "global", "esr", "swlb"])
def test_parity_routing_nic_cross(routing, nic):
    spec = get_scenario("flap_during_incast").with_sim(
        slots=160, routing=routing, nic=nic)
    ref, jres = _run_both(spec)
    _assert_parity(spec, ref, jres)


@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
@pytest.mark.parametrize("nic", ["spx", "dcqcn", "global", "esr", "swlb"])
def test_parity_fat_tree_routing_nic_cross(routing, nic):
    """Fat-tree twin of the routing x nic cross: core-tier faults plus
    random two-stage failures on the 3-tier testbed.  The numpy
    fat-tree step mirrors the jx pair-aggregated op order, so parity
    holds at machine precision even where AR's symmetric fractions park
    queues on quantization-bin edges."""
    from dataclasses import replace

    from repro.scenarios import FaultSpec
    spec = get_scenario("ft_core_failure_resiliency")
    spec = replace(spec, faults=spec.faults + (
        FaultSpec("random_fail", start_slot=60, frac=0.15),
        FaultSpec("link_kill", start_slot=45, leaf=0, spine=1),))
    spec = spec.with_sim(slots=160, routing=routing, nic=nic)
    ref, jres = _run_both(spec)
    _assert_parity(spec, ref, jres)


def test_parity_swlb_delayed_exclusion():
    """swlb's software-timescale plane exclusion (pending_fail firing)
    must match: run fig12 long enough for the delayed reaction."""
    spec = get_scenario("fig12_plane_flap").with_sim(nic="swlb",
                                                     slots=2000)
    ref, jres = _run_both(spec)
    _assert_parity(spec, ref, jres)


@pytest.mark.parametrize("name,kw", [
    ("fig9_victim_noise", dict(slots=120)),           # two tenants, AR
    ("fig12_plane_flap", dict()),                     # 4 planes, probe loss
    ("cascading_spine_loss", dict(slots=200)),        # WAR + cascade
    ("allreduce_under_random_failures", dict()),      # finite transfers
    ("straggler_failure_compound", dict(slots=200)),  # compound faults
])
def test_parity_representative(name, kw):
    spec = get_scenario(name).with_sim(**kw) if kw else get_scenario(name)
    ref, jres = _run_both(spec)
    _assert_parity(spec, ref, jres)


@pytest.mark.parametrize("routing", ["ecmp", "war"])
@pytest.mark.parametrize("name,mode", [
    ("reroute_random_failures", "backup"),      # leaf-spine backup table
    ("reroute_random_failures", "rehash"),      # post-detect re-draw
    ("reroute_random_failures_ft", "backup"),   # two-stage backup chain
    ("poisson_flap_storm", "backup"),           # flap storm + reaction
])
def test_parity_reaction(name, mode, routing):
    """Reaction-layer parity: the lagged visible-topology twin, the
    blackhole accumulator, and the backup/rehash reassignments must all
    agree across backends — including the new blackhole_timeline and
    reaction_slots observables."""
    from dataclasses import replace

    spec = get_scenario(name)
    spec = replace(spec, reaction=replace(spec.reaction, mode=mode))
    spec = spec.with_sim(slots=200, routing=routing)
    ref, jres = _run_both(spec)
    assert ref.blackhole_timeline is not None
    _assert_parity(spec, ref, jres)


def test_every_registry_scenario_has_static_timeline():
    for name in list_scenarios():
        spec = get_scenario(name)
        assert has_static_timeline(spec)
        tl = compile_fault_timeline(spec)
        assert tl.up.shape[0] == spec.sim.slots


def test_dynamic_event_closures_rejected():
    import dataclasses
    spec = get_scenario("fig8_bisection")
    bogus = dataclasses.replace(spec, faults=(lambda t, topo: None,))
    with pytest.raises(ValueError, match="dynamic"):
        compile_fault_timeline(bogus)


# ---------------------------------------------------------------------------
# slow: full-length parity over the whole registry + batched sweeps
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
@pytest.mark.parametrize("nic", ["spx", "dcqcn"])
@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_parity_full_registry_cross(name, routing, nic):
    """The acceptance claim verbatim: every registry scenario, full
    length, across ar|war|ecmp x spx|dcqcn, within 1e-5 in float64.
    Giga-scale scenarios (>= 4096 hosts) use the contained-fork
    criterion instead — see _assert_parity_chaotic."""
    spec = get_scenario(name).with_sim(routing=routing, nic=nic)
    ref, jres = _run_both(spec)
    n_hosts = spec.topo.n_leaves * spec.topo.hosts_per_leaf
    if n_hosts >= 4096:
        _assert_parity_chaotic(spec, ref, jres)
    else:
        _assert_parity(spec, ref, jres)


@pytest.mark.slow
@pytest.mark.parametrize("routing", ["ar", "ecmp"])
def test_parity_batched_sweep_matches_serial(routing):
    grid = SweepGrid(seeds=(0, 1, 2), routings=(routing,), slots=150)
    with jax.enable_x64(True):
        serial = sweep("fig9_victim_noise", grid, processes=1)
        batched = sweep("fig9_victim_noise", grid, backend="jax")
    assert [m.to_row() for m in serial] == [m.to_row() for m in batched]
