"""Megabatch dispatch: one fused launch per sweep, row-identical to the
per-group path.

The megabatch path lifts routing/nic into per-element traced branch
selectors and stacks whole grids into one `jit(vmap)` launch
(`repro.netsim.jx.megabatch`).  These tests pin:

  * row-identity (1e-5, x64) against the per-group executor across the
    full routing × nic cross, mixed fault timelines (fault axes with
    differing segment counts), and mixed scenarios whose flow counts
    land in different padding buckets;
  * the single-launch property: a multi-axis grid = 1 dispatch and 1
    program compile (the bench JSON's acceptance metric);
  * the `_jitted` device-fingerprint regression (a pmap built for N
    devices must not be reused when the device set changes).
"""
import jax
import numpy as np
import pytest

from repro.experiments import Axis, Experiment, execute_points, product
from repro.netsim.jx import dispatch_stats, reset_dispatch_stats
from repro.netsim.jx import engine
from repro.scenarios import list_scenarios

TOL = 1e-5


def _grid_points(base, axes):
    exp = Experiment(name=f"test_megabatch.{base}", base=base,
                     axes=product(*axes))
    return [p.spec for p in exp.points()]


def _run_both(points):
    with jax.enable_x64(True):
        group = execute_points(points, backend="jax",
                               jx_dispatch="group")
        mega = execute_points(points, backend="jax",
                              jx_dispatch="megabatch")
    return group, mega


def _assert_rows_identical(points, group, mega):
    for p, a, b in zip(points, group, mega):
        where = f"{p.name} {p.sim.routing}/{p.sim.nic} seed={p.sim.seed}"
        assert b.to_row() == a.to_row(), where
        assert b.mean_goodput == pytest.approx(a.mean_goodput, abs=TOL)
        assert b.isolation_index == pytest.approx(a.isolation_index,
                                                  abs=TOL)
        assert b.recovery_slots == a.recovery_slots, where
        for t in a.tenant_mean:
            assert b.tenant_mean[t] == pytest.approx(a.tenant_mean[t],
                                                     abs=TOL)
            assert b.tenant_p01[t] == pytest.approx(a.tenant_p01[t],
                                                    abs=TOL)
        if not (np.isnan(a.completion_tail)
                and np.isnan(b.completion_tail)):
            assert b.completion_tail == pytest.approx(a.completion_tail,
                                                      abs=TOL)


def test_megabatch_full_routing_nic_cross_row_identity():
    """The acceptance claim: every (routing, nic) pair of the registry
    cross, fused into one launch, matches the per-group dispatch."""
    points = _grid_points("flap_during_incast", [
        Axis("sim.routing", ("ar", "war", "ecmp")),
        Axis("sim.nic", ("spx", "dcqcn", "global", "esr", "swlb")),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (100,)),
    ])
    reset_dispatch_stats()
    group, mega = _run_both(points)
    _assert_rows_identical(points, group, mega)


def test_megabatch_mixed_fault_timelines():
    """Fault axes change the timeline data and the number of
    piecewise-constant segments per point — segment-count padding must
    stay inert."""
    points = _grid_points("flap_during_incast", [
        Axis("sim.routing", ("ar", "ecmp")),
        Axis("faults[0].frac", (0.3, 0.9)),
        Axis("faults[0].period", (40, 70)),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (160,)),
    ])
    group, mega = _run_both(points)
    _assert_rows_identical(points, group, mega)


def test_megabatch_mixed_scenarios_flow_buckets():
    """Scenarios with different flow populations share a launch when
    they land in the same power-of-two flow bucket (60 and 64 flows ->
    bucket 64) and split into another when they don't (30 -> 32); the
    finite-transfer scenario also exercises completion slots through
    the flow padding."""
    points = _grid_points(None, [
        Axis("scenario", ("flap_during_incast",
                          "allreduce_under_random_failures",
                          "staggered_incast_bursts")),
        Axis("sim.routing", ("ar", "ecmp")),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (120,)),
    ])
    reset_dispatch_stats()
    group, mega = _run_both(points)
    stats = dispatch_stats()
    # megabatch: two flow buckets -> exactly 2 fused launches for 12
    # points (the per-group path dispatched 6 structures before it)
    assert stats["dispatches"] - 6 == 2
    _assert_rows_identical(points, group, mega)


def test_megabatch_multi_axis_grid_single_compile():
    """A 3-axis grid (nic x fault x seed) is ONE dispatch and ONE
    program compile — the dispatch-count metric CI asserts from
    BENCH_backend.json.  slots=101 keeps the program fingerprint unique
    to this test regardless of suite order."""
    points = _grid_points("flap_during_incast", [
        Axis("sim.routing", ("ar", "war", "ecmp")),
        Axis("sim.nic", ("spx", "dcqcn")),
        Axis("faults[0].frac", (0.4, 0.8)),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (101,)),
    ])
    reset_dispatch_stats()
    execute_points(points, backend="jax", jx_dispatch="megabatch")
    stats = dispatch_stats()
    assert stats["dispatches"] == 1
    assert stats["compiles"] == 1
    # warm re-run: same program, no new compile
    reset_dispatch_stats()
    execute_points(points, backend="jax", jx_dispatch="megabatch")
    stats = dispatch_stats()
    assert stats["dispatches"] == 1
    assert stats["compiles"] == 0


def test_megabatch_mixed_topology_kinds_one_compile_per_bucket():
    """A grid mixing leaf_spine and fat_tree points (the topology-axis
    experiment shape) fuses into exactly one launch and one program
    compile per topology-kind shape bucket, row-identical to the
    per-group dispatch."""
    points = _grid_points(None, [
        Axis("scenario", ("bisection_multiplane", "bisection_fat_tree")),
        Axis("sim.routing", ("war", "ecmp")),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (200,)),      # random_fail at 150 still fires
    ])
    reset_dispatch_stats()
    with jax.enable_x64(True):
        mega = execute_points(points, backend="jax",
                              jx_dispatch="megabatch")
    stats = dispatch_stats()
    assert stats["dispatches"] == 2, stats   # one per topology kind
    assert stats["compiles"] == 2, stats
    with jax.enable_x64(True):
        group = execute_points(points, backend="jax", jx_dispatch="group")
    _assert_rows_identical(points, group, mega)


def test_jitted_rebuilds_on_device_set_change(monkeypatch):
    """Regression: `_jitted` used to key its memo on `JxConfig` only, so
    a pmap callable built for N host devices was silently reused after
    the visible device set changed."""
    from repro.scenarios import compile_scenario, get_scenario

    spec = get_scenario("flap_during_incast").with_sim(slots=50)
    cfg = engine.JxConfig.from_sim(compile_scenario(spec).cfg, spec.topo)
    fn_a = engine._jitted(cfg, batched=True, n_shards=1)
    assert engine._jitted(cfg, batched=True, n_shards=1) is fn_a
    monkeypatch.setattr(engine, "_device_fingerprint",
                        lambda: (("cpu", 0), ("cpu", 1)))
    fn_b = engine._jitted(cfg, batched=True, n_shards=1)
    assert fn_b is not fn_a
    monkeypatch.undo()
    assert engine._jitted(cfg, batched=True, n_shards=1) is fn_a


def test_stack_idx_covers_every_routing_nic():
    from repro.scenarios.spec import NICS, ROUTINGS

    seen = set()
    for r in ROUTINGS:
        for n in NICS:
            row = engine.stack_idx_for(r, n)
            assert row[0] in (engine.ROUTE_PAIR, engine.ROUTE_ECMP)
            assert (row[0] == engine.ROUTE_ECMP) == (r == "ecmp")
            assert row[1] == (r == "war")
            assert row[3] == (n == "esr")
            seen.add(row)
    # every (routing, nic) pair maps to a distinct selector row
    # (global/esr share branch indices but differ in is_esr)
    assert len(seen) == len(ROUTINGS) * len(NICS)


@pytest.mark.slow
@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
@pytest.mark.parametrize("nic", ["spx", "dcqcn"])
def test_megabatch_registry_wide_row_identity(routing, nic):
    """Registry-wide: every scenario (mixed flow buckets, timelines,
    finite transfers) through one executor call per (routing, nic),
    megabatch vs per-group.  Schedule scenarios pin their own horizon
    (the compiler rejects a sim too short for every training step), so
    they keep their registry slots instead of the 150-slot shrink."""
    from repro.scenarios import get_scenario
    sched = tuple(n for n in list_scenarios() if any(
        w.kind == "schedule" for w in get_scenario(n).workloads))
    rest = tuple(n for n in list_scenarios() if n not in sched)
    points = _grid_points(None, [
        Axis("scenario", rest),
        Axis("sim.routing", (routing,)),
        Axis("sim.nic", (nic,)),
        Axis("sim.slots", (150,)),
    ]) + _grid_points(None, [
        Axis("scenario", sched),
        Axis("sim.routing", (routing,)),
        Axis("sim.nic", (nic,)),
    ])
    group, mega = _run_both(points)
    _assert_rows_identical(points, group, mega)
