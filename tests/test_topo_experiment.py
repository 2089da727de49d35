"""ISSUE 5 acceptance: the registered topology-axis experiment
(`topo_kind_resiliency`) sweeps kind ∈ {leaf_spine, fat_tree} x routing
x fault-frac through the megabatch path with numpy↔jax row parity at
1e-5 (x64), and the multiplane fabric shows strictly higher post-failure
bisection throughput than the equal-cost fat-tree in the resiliency
scenario."""
import jax
import numpy as np
import pytest

from repro.experiments import (Axis, Experiment, get_experiment, product,
                               run_experiment)
from repro.scenarios import get_scenario, run_point

TOL = 1e-5


def _row_parity(exp):
    rs_np = run_experiment(exp, backend="numpy", processes=2)
    with jax.enable_x64(True):
        rs_jx = run_experiment(exp, backend="jax")   # megabatch default
    rows_np, rows_jx = rs_np.to_metrics(), rs_jx.to_metrics()
    assert len(rows_np) == len(rows_jx) == len(exp.points())
    kinds = set()
    for p, a, b in zip(exp.points(), rows_np, rows_jx):
        kinds.add(p.spec.topo.kind)
        where = f"{a.scenario} {a.routing} {p.coords}"
        assert b.mean_goodput == pytest.approx(a.mean_goodput,
                                               abs=TOL), where
        assert b.isolation_index == pytest.approx(a.isolation_index,
                                                  abs=TOL), where
        assert b.recovery_slots == a.recovery_slots, where
        for key in ("post_failure_bw", "post_failure_p01"):
            assert b.extra[key] == pytest.approx(a.extra[key],
                                                 abs=TOL), where
    assert kinds == {"leaf_spine", "fat_tree"}
    return rows_np


def test_topo_kind_experiment_megabatch_row_parity():
    """Reduced-horizon version of the registered grid for tier-1: same
    axes, slots cut to 200 (the slot-150 fault still fires)."""
    base = get_experiment("topo_kind_resiliency")
    exp = Experiment(name="topo_kind_resiliency.t1",
                     axes=product(base.grid(),
                                  Axis("sim.slots", (200,))),
                     derive=base.derive)
    _row_parity(exp)


@pytest.mark.slow
def test_topo_kind_experiment_full_length():
    """The registered experiment verbatim, both backends."""
    _row_parity(get_experiment("topo_kind_resiliency"))


def test_multiplane_beats_equal_cost_fat_tree_post_failure():
    """The §3.1 headline, strict: at the resiliency scenario's operating
    point (25% uniform link failures, SPX + weighted-AR) the flat
    multiplane's post-failure bisection throughput exceeds the
    equal-bisection fat-tree's — the 4-hop cross-pod min-cuts strand
    surviving capacity that the 2-hop multiplane keeps usable."""
    ls = run_point(get_scenario("bisection_multiplane"))
    ft = run_point(get_scenario("bisection_fat_tree"))
    assert np.isfinite(ls.mean_goodput) and np.isfinite(ft.mean_goodput)
    assert ls.mean_goodput > ft.mean_goodput, (ls.mean_goodput,
                                               ft.mean_goodput)
    # the margin is structural (~30%+ across seeds), not noise
    assert ls.mean_goodput > 1.15 * ft.mean_goodput
