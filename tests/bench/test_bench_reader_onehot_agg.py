"""The `onehot_agg_share` reader against the program's launch counters:
the share of launched flow-slots whose sparse sums were compiled as
one-hot contractions, and nothing on a program without the counter."""
from dataclasses import replace

import pytest

import run


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_onehot_agg_share_reads_the_contracted_share(monkeypatch):
    from repro.experiments import execute_points
    from repro.netsim import flight
    from repro.netsim.jx import megabatch
    from repro.scenarios import get_scenario

    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=10, backend="jax", routing="ecmp")
    points = [spec, spec.with_sim(routing="war")]
    read = run.load_reader("onehot_agg_share")
    flight.reset_dispatch_stats()
    assert read({}) is None                  # nothing launched yet
    execute_points(points)                   # the CPU scatters
    assert read({}) == 0.0
    struct_cfg = megabatch._struct_cfg
    monkeypatch.setattr(megabatch, "_struct_cfg", lambda c: replace(
        struct_cfg(c), ecmp_onehot_bytes=1 << 30))
    flight.reset_dispatch_stats()
    execute_points(points)                   # ECMP and WAR rows alike
    assert read({}) == pytest.approx(100.0)
    # dense aggregation keeps its gather plans
    monkeypatch.setenv("REPRO_JX_AGG", "dense")
    flight.reset_dispatch_stats()
    execute_points(points)
    assert read({}) == 0.0
    # a program without the counter reads nothing
    monkeypatch.setattr(flight, "dispatch_counts",
                        lambda: {"flow_slots_launched": 10})
    assert read({}) is None
