"""Megabatch dispatch: one fused launch per sweep, row-identical to
each point run alone.

The megabatch path lifts routing/nic into per-element traced branch
selectors and stacks whole grids into one `jit(vmap)` launch
(`repro.netsim.jx.megabatch`).  These tests pin:

  * row-identity (1e-5, x64) of the fused grid against each point run
    alone through `run_compiled` (a one-lane megabatch) across the full
    routing × nic cross, mixed fault timelines (fault axes with
    differing segment counts), and mixed scenarios whose flow counts
    land in different padding buckets — so lane packing, bucket
    padding and the deduplicated ECMP plan table stay inert;
  * the single-launch property: a multi-axis grid = 1 dispatch and 1
    program compile;
  * the `_jitted_mb` device-fingerprint regression (a program sharded
    over N devices must not be reused when the device set changes).
"""
import jax
import numpy as np
import pytest

from repro.experiments import Axis, Experiment, execute_points, product
from repro.netsim.jx import dispatch_stats, reset_dispatch_stats
from repro.netsim.jx import engine
from repro.scenarios import compile_scenario, distill_metrics, list_scenarios

TOL = 1e-5


def _grid_points(base, axes):
    exp = Experiment(name=f"test_megabatch.{base}", base=base,
                     axes=product(*axes))
    return [p.spec for p in exp.points()]


def _run_both(points):
    """Metrics of each point run alone (`run_compiled`), then of the
    fused grid, at x64."""
    with jax.enable_x64(True):
        alone = []
        for p in points:
            c = compile_scenario(p)
            alone.append(distill_metrics(p, c, engine.run_compiled(c)))
        mega = execute_points(points, backend="jax")
    return alone, mega


def _assert_rows_identical(points, alone, mega):
    for p, a, b in zip(points, alone, mega):
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
    cross, fused into one launch, matches each point run alone."""
    points = _grid_points("flap_during_incast", [
        Axis("sim.routing", ("ar", "war", "ecmp")),
        Axis("sim.nic", ("spx", "dcqcn", "global", "esr", "swlb")),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (100,)),
    ])
    alone, mega = _run_both(points)
    _assert_rows_identical(points, alone, mega)


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
    alone, mega = _run_both(points)
    _assert_rows_identical(points, alone, mega)


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
    alone, mega = _run_both(points)
    stats = dispatch_stats()
    # two flow buckets -> exactly 2 fused launches for 12 points (after
    # the 12 one-point launches of the points run alone)
    assert stats["dispatches"] - len(points) == 2
    _assert_rows_identical(points, alone, mega)


def test_megabatch_multi_axis_grid_single_compile():
    """A 3-axis grid (nic x fault x seed) is ONE dispatch and ONE
    program compile.  slots=101 keeps the program fingerprint unique
    to this test regardless of suite order."""
    points = _grid_points("flap_during_incast", [
        Axis("sim.routing", ("ar", "war", "ecmp")),
        Axis("sim.nic", ("spx", "dcqcn")),
        Axis("faults[0].frac", (0.4, 0.8)),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (101,)),
    ])
    reset_dispatch_stats()
    execute_points(points, backend="jax")
    stats = dispatch_stats()
    assert stats["dispatches"] == 1
    assert stats["compiles"] == 1
    # warm re-run: same program, no new compile
    reset_dispatch_stats()
    execute_points(points, backend="jax")
    stats = dispatch_stats()
    assert stats["dispatches"] == 1
    assert stats["compiles"] == 0


def test_megabatch_mixed_topology_kinds_one_compile_per_bucket():
    """A grid mixing leaf_spine and fat_tree points (the topology-axis
    experiment shape) fuses into exactly one launch and one program
    compile per topology-kind shape bucket, row-identical to each point
    run alone."""
    points = _grid_points(None, [
        Axis("scenario", ("bisection_multiplane", "bisection_fat_tree")),
        Axis("sim.routing", ("war", "ecmp")),
        Axis("seed", (0, 1)),
        Axis("sim.slots", (200,)),      # random_fail at 150 still fires
    ])
    reset_dispatch_stats()
    with jax.enable_x64(True):
        mega = execute_points(points, backend="jax")
    stats = dispatch_stats()
    assert stats["dispatches"] == 2, stats   # one per topology kind
    assert stats["compiles"] == 2, stats
    alone, _ = _run_both(points)
    _assert_rows_identical(points, alone, mega)


def test_jitted_rebuilds_on_device_set_change(monkeypatch):
    """Regression: a jit memo keyed on `JxConfig` only would silently
    reuse a program built for N devices after the visible device set
    changed; `_jitted_mb` keys on the device fingerprint too."""
    from repro.netsim.jx import megabatch
    from repro.scenarios import get_scenario

    spec = get_scenario("flap_during_incast").with_sim(slots=50)
    cfg = megabatch._struct_cfg(compile_scenario(spec))
    lanes = ((engine.ROUTE_PAIR, 1),)
    fn_a = engine._jitted_mb(cfg, 1, lanes)
    assert engine._jitted_mb(cfg, 1, lanes) is fn_a
    monkeypatch.setattr(engine, "_device_fingerprint",
                        lambda: (("cpu", 0), ("cpu", 1)))
    fn_b = engine._jitted_mb(cfg, 1, lanes)
    assert fn_b is not fn_a
    monkeypatch.undo()
    assert engine._jitted_mb(cfg, 1, lanes) is fn_a


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
    against each point run alone.  Schedule scenarios pin their own horizon
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
    alone, mega = _run_both(points)
    _assert_rows_identical(points, alone, mega)
