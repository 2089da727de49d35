"""Scenario engine: spec -> compile determinism, registry completeness,
fault schedules, runner metrics, and planes.apportion edge cases."""
import numpy as np
import pytest

from repro.core.planes import apportion, plane_loads
from repro.scenarios import (FaultSpec, ScenarioSpec, SimSpec, SweepGrid,
                             TenantSpec, TopologySpec, WorkloadSpec,
                             compile_scenario, get_scenario,
                             list_scenarios, run_point, sweep)

SMALL = TopologySpec(n_leaves=2, n_spines=2, hosts_per_leaf=2)


def _flow_tuples(flows):
    return [(f.src, f.dst, f.demand, f.bytes_total, f.group, f.start_slot)
            for f in flows]


# ---------------------------------------------------------------------------
# compile determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["fig8_bisection", "permutation_stress",
                                  "storage_background_mix"])
def test_compile_is_deterministic(name):
    spec = get_scenario(name)
    a = compile_scenario(spec)
    b = compile_scenario(spec)
    assert _flow_tuples(a.flows) == _flow_tuples(b.flows)
    assert a.fault_slots == b.fault_slots
    assert a.tenants == b.tenants


def test_workload_seed_changes_random_draws():
    spec = get_scenario("permutation_stress")
    a = compile_scenario(spec)
    b = compile_scenario(spec.with_workload_seed(spec.workload_seed + 1))
    assert _flow_tuples(a.flows) != _flow_tuples(b.flows)


def test_same_seed_identical_sim_trajectory():
    spec = get_scenario("straggler_failure_compound").with_sim(slots=60)
    r1 = compile_scenario(spec).run()
    r2 = compile_scenario(spec).run()
    np.testing.assert_array_equal(r1.goodput, r2.goodput)
    np.testing.assert_array_equal(r1.completion_slot, r2.completion_slot)


# ---------------------------------------------------------------------------
# registry completeness
# ---------------------------------------------------------------------------

def test_registry_has_required_coverage():
    names = list_scenarios()
    assert len(names) >= 10
    ports = [n for n in names if n.startswith("fig")]
    assert len(ports) >= 4
    assert len(names) - len(ports) >= 6


@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_every_scenario_compiles_and_runs(name):
    spec = get_scenario(name)
    if not any(w.kind == "schedule" for w in spec.workloads):
        # schedule scenarios pin their own horizon (the compiler
        # rejects a sim too short to hold every training step)
        spec = spec.with_sim(slots=50)
    c = compile_scenario(spec)
    assert len(c.flows) > 0
    m = run_point(spec)
    assert np.isfinite(m.mean_goodput) and m.mean_goodput >= 0
    assert 0.0 < m.isolation_index <= 1.0 + 1e-9
    assert set(m.tenant_mean) == set(m.tenant_p99) == set(m.tenant_p01)
    for t, v in m.tenant_mean.items():
        assert np.isfinite(v)
        assert m.tenant_p01[t] <= m.tenant_p99[t] + 1e-12


# ---------------------------------------------------------------------------
# tenants / workloads / faults
# ---------------------------------------------------------------------------

def test_tenant_overlap_rejected():
    spec = ScenarioSpec(
        name="overlap", topo=SMALL,
        tenants=(TenantSpec("a", placement="block", n_hosts=3),
                 TenantSpec("b", placement="block", offset=2, n_hosts=2)),
        workloads=(WorkloadSpec("all2all", tenant="a"),))
    with pytest.raises(ValueError, match="overlap"):
        compile_scenario(spec)


def test_unknown_kinds_rejected():
    with pytest.raises(ValueError, match="workload"):
        ScenarioSpec(name="bad", topo=SMALL,
                     workloads=(WorkloadSpec("warp"),)).validate()
    with pytest.raises(ValueError, match="fault"):
        ScenarioSpec(name="bad", topo=SMALL,
                     workloads=(WorkloadSpec("all2all"),),
                     faults=(FaultSpec("meteor"),)).validate()


def _spec_with_fault(fault, topo=SMALL):
    return ScenarioSpec(name="bounds", topo=topo,
                        workloads=(WorkloadSpec("all2all"),),
                        faults=(fault,))


def test_fault_indices_bound_checked():
    """Regression (ISSUE 5 satellite): out-of-range fault indices used
    to pass validation and die with a bare IndexError — or silently
    wrap via negative indexing — deep in the event closures / the jx
    timeline compiler.  They must raise `FaultBoundsError` at
    `validate()` time."""
    from repro.scenarios.spec import FaultBoundsError

    bad = [
        FaultSpec("link_kill", plane=2),               # n_planes = 1
        FaultSpec("link_kill", plane=-2),              # only -1 = all
        FaultSpec("link_kill", leaf=2),                # n_leaves = 2
        FaultSpec("link_kill", spine=-1),
        FaultSpec("link_flap", period=4, spine=2),     # n_spines = 2
        FaultSpec("leaf_trim", leaf=-1),
        FaultSpec("cascade", period=4, spines=(0, 2)),
        FaultSpec("access_kill", host=4),              # n_hosts = 4
        FaultSpec("access_flap", period=4, host=-1),
        FaultSpec("straggler", host=17),
        FaultSpec("core_kill"),                        # not a fat_tree
    ]
    for fault in bad:
        with pytest.raises(FaultBoundsError):
            _spec_with_fault(fault).validate()

    ft = TopologySpec(kind="fat_tree", n_leaves=2, hosts_per_leaf=2,
                      n_pods=2, n_aggs=2, n_cores=4)
    bad_ft = [
        FaultSpec("link_kill", spine=2),               # n_aggs = 2
        FaultSpec("core_kill", pod=2),                 # n_pods = 2
        FaultSpec("core_kill", core=4),                # n_cores = 4
        FaultSpec("cascade", period=4, spines=(0,), pod=-1),
    ]
    for fault in bad_ft:
        with pytest.raises(FaultBoundsError):
            _spec_with_fault(fault, ft).validate()

    # in-range faults (including the fat-tree agg addressing) still pass
    _spec_with_fault(FaultSpec("link_kill", leaf=1, spine=1)).validate()
    _spec_with_fault(FaultSpec("random_fail", plane=-1, frac=0.5)).validate()
    _spec_with_fault(FaultSpec("core_kill", pod=1, core=3), ft).validate()
    _spec_with_fault(FaultSpec("cascade", period=4, spines=(1,), pod=1),
                     ft).validate()


def test_fat_tree_topology_shape_validated():
    with pytest.raises(ValueError, match="n_pods"):
        TopologySpec(kind="fat_tree", n_pods=1).validate()
    with pytest.raises(ValueError, match="divisible"):
        TopologySpec(kind="fat_tree", n_leaves=3, n_pods=2).validate()
    with pytest.raises(ValueError, match="n_cores"):
        TopologySpec(kind="fat_tree", n_pods=2, n_aggs=3,
                     n_cores=4).validate()
    with pytest.raises(ValueError, match="kind"):
        TopologySpec(kind="clos").validate()


def test_flap_schedule_restores_capacity():
    spec = ScenarioSpec(
        name="flap", topo=SMALL,
        tenants=(TenantSpec("main"),),
        workloads=(WorkloadSpec("pairs", pairs=((0, 2),)),),
        faults=(FaultSpec("link_flap", start_slot=4, stop_slot=20,
                          period=8, duty=0.5, leaf=0, spine=0),),
        sim=SimSpec(slots=30))
    c = compile_scenario(spec)
    # transitions at every period start inside [start, stop)
    assert [s for s, _ in c.fault_slots] == [4, 12]
    cap = spec.topo.uplink_cap
    up = []
    for t in range(30):
        c.events(t, c.topo)
        up.append(c.topo.up[0, 0, 0])
    assert up[4] == 0.0 and up[8] == cap     # down then restored
    assert up[12] == 0.0 and up[16] == cap   # second flap cycle
    assert up[29] == cap                      # healthy after stop


def test_straggler_slows_then_restores():
    spec = ScenarioSpec(
        name="strag", topo=SMALL,
        tenants=(TenantSpec("main"),),
        workloads=(WorkloadSpec("allreduce"),),
        faults=(FaultSpec("straggler", start_slot=2, stop_slot=6, host=1,
                          frac=0.25, plane=-1),),
        sim=SimSpec(slots=10))
    c = compile_scenario(spec)
    for t in range(10):
        c.events(t, c.topo)
        if 2 <= t < 6:
            assert np.allclose(c.topo.access[:, 1], 0.25)
        if t >= 6:
            assert np.allclose(c.topo.access[:, 1], 1.0)


def test_cascade_kills_spines_in_order():
    spec = ScenarioSpec(
        name="casc", topo=SMALL,
        tenants=(TenantSpec("main"),),
        workloads=(WorkloadSpec("all2all"),),
        faults=(FaultSpec("cascade", start_slot=1, period=3,
                          spines=(1, 0)),),
        sim=SimSpec(slots=8))
    c = compile_scenario(spec)
    for t in range(5):
        c.events(t, c.topo)
    assert (c.topo.up[0, :, 1] == 0).all()     # spine 1 died at t=1
    assert (c.topo.up[0, :, 0] == 0).all()     # spine 0 died at t=4
    assert [lbl for _, lbl in c.fault_slots] == ["cascade[0]",
                                                 "cascade[1]"]


# ---------------------------------------------------------------------------
# runner metrics
# ---------------------------------------------------------------------------

def test_sweep_grid_shape_and_inheritance():
    spec = get_scenario("fig11_degraded_leaf")
    grid = SweepGrid(seeds=(0, 1), slots=40)
    points = grid.points(spec)
    assert len(points) == 2
    # routing/nic inherit from the spec when the grid leaves them None
    assert all(p.sim.routing == "war" and p.sim.nic == "spx"
               for p in points)
    assert points[0].sim.seed != points[1].sim.seed
    assert points[0].workload_seed != points[1].workload_seed


def test_sweep_grid_rejects_unknown_routing_and_nic():
    spec = get_scenario("fig8_bisection")
    with pytest.raises(ValueError, match="unknown routing 'warp'"):
        SweepGrid(routings=("ar", "warp")).points(spec)
    with pytest.raises(ValueError, match="unknown nic 'tcp'"):
        SweepGrid(nics=("tcp",)).points(spec)


def test_sweep_grid_rejects_empty_tuples():
    # () used to silently fall back to the spec's own routing/nic —
    # only None may inherit
    spec = get_scenario("fig8_bisection")
    with pytest.raises(ValueError, match="empty routings"):
        SweepGrid(routings=()).points(spec)
    with pytest.raises(ValueError, match="empty nics"):
        SweepGrid(nics=()).points(spec)


def test_pairs_endpoints_validated():
    out_of_range = ScenarioSpec(
        name="bad_pairs", topo=SMALL, tenants=(TenantSpec("main"),),
        workloads=(WorkloadSpec("pairs", pairs=((0, 99),)),))
    with pytest.raises(ValueError, match="pairs endpoints"):
        out_of_range.validate()
    negative = ScenarioSpec(
        name="neg_pairs", topo=SMALL, tenants=(TenantSpec("main"),),
        workloads=(WorkloadSpec("pairs", pairs=((0, -3),)),))
    with pytest.raises(ValueError, match="pairs endpoints"):
        negative.validate()
    foreign = ScenarioSpec(
        name="foreign_pairs", topo=SMALL,
        tenants=(TenantSpec("a", placement="block", n_hosts=2),
                 TenantSpec("b", placement="remainder")),
        workloads=(WorkloadSpec("pairs", tenant="a", pairs=((0, 3),)),))
    with pytest.raises(ValueError, match="outside the tenant"):
        compile_scenario(foreign)


def test_duplicate_explicit_tenant_hosts_rejected():
    spec = ScenarioSpec(
        name="dup_hosts", topo=SMALL,
        tenants=(TenantSpec("a", placement="explicit", hosts=(1, 1, 2)),),
        workloads=(WorkloadSpec("all2all", tenant="a"),))
    with pytest.raises(ValueError, match="more than once"):
        compile_scenario(spec)


def test_unknown_backend_rejected():
    spec = get_scenario("fig8_bisection").with_sim(slots=20)
    with pytest.raises(ValueError, match="backend"):
        spec.with_sim(backend="torch").validate()
    with pytest.raises(ValueError, match="backend"):
        compile_scenario(spec).run(backend="torch")


def test_backend_field_dispatches_jax():
    spec = get_scenario("fig12_plane_flap").with_sim(slots=80,
                                                     backend="jax")
    import jax

    with jax.enable_x64(True):   # f32 trajectories may fork at CC thresholds
        m = run_point(spec)
        ref = run_point(spec.with_sim(backend="numpy"))
    assert m.mean_goodput == pytest.approx(ref.mean_goodput, abs=1e-5)


def test_sweep_backend_override_beats_spec_backend():
    # sweep(backend="numpy") must not silently run jax-backend specs on
    # JAX: the engine's dispatch flag stays untouched
    import sys
    from repro.scenarios import sweep
    spec = get_scenario("fig12_plane_flap").with_sim(slots=40,
                                                     backend="jax")
    engine = sys.modules.get("repro.netsim.jx.engine")
    was = getattr(engine, "_BACKEND_USED", False) if engine else False
    try:
        if engine is not None:
            engine._BACKEND_USED = False
        sweep(spec, SweepGrid(seeds=(0,)), backend="numpy")
        engine = sys.modules.get("repro.netsim.jx.engine")
        assert not getattr(engine, "_BACKEND_USED", False)
    finally:
        if engine is not None:
            engine._BACKEND_USED = was


def test_sweep_parallel_matches_serial():
    grid = SweepGrid(seeds=(0, 1), slots=40)
    serial = sweep("multi_tenant_50_50", grid, processes=1)
    parallel = sweep("multi_tenant_50_50", grid, processes=2)
    assert [m.to_row() for m in serial] == [m.to_row() for m in parallel]


def test_recovery_reported_for_fault_scenarios():
    m = run_point(get_scenario("fig12_plane_flap"))
    assert len(m.recovery_slots) == 1
    slot, label, rec = m.recovery_slots[0]
    assert slot == 50 and label == "access_kill"
    assert 0 < rec < 20       # hardware PLB: a handful of slots


def test_completion_tail_on_finite_transfers():
    m = run_point(get_scenario("allreduce_under_random_failures"))
    assert np.isfinite(m.completion_tail)
    assert m.completion_tail >= 1.0


def test_symmetry_outliers_flag_injected_asymmetry():
    healthy = run_point(get_scenario("fig8_bisection").with_sim(slots=80))
    degraded = run_point(get_scenario("fig11_degraded_leaf")
                         .with_sim(slots=80))
    assert healthy.symmetry_cv < degraded.symmetry_cv


# ---------------------------------------------------------------------------
# planes.apportion edge cases (satellite)
# ---------------------------------------------------------------------------

def test_apportion_all_zero_weights_uniform():
    a = apportion(np.zeros(4), 8)
    loads = plane_loads(a, 4, 1.0)
    np.testing.assert_array_equal(loads, np.full(4, 2.0))


def test_apportion_k_equals_n_planes():
    a = apportion(np.ones(6), 6)
    loads = plane_loads(a, 6, 1.0)
    np.testing.assert_array_equal(loads, np.ones(6))


def test_apportion_k_equals_n_planes_with_dead_plane():
    a = apportion(np.array([1.0, 0.0, 1.0, 1.0]), 4)
    loads = plane_loads(a, 4, 1.0)
    assert loads[1] == 0.0
    assert loads.sum() == 4


def test_apportion_single_chunk():
    a = apportion(np.array([0.2, 0.8]), 1)
    assert a.shape == (1,)
    assert a[0] == 1
