"""The `ecmp_onehot_share` reader against the program's launch counters:
the share of launched flow-slots whose ECMP link lookups were compiled
as a one-hot contraction, and nothing on a program without the
counter."""
from dataclasses import replace

import pytest

import run


def test_ecmp_onehot_share_reads_the_contracted_share(monkeypatch):
    from repro.experiments import execute_points
    from repro.netsim import flight
    from repro.netsim.jx import megabatch
    from repro.scenarios import get_scenario

    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=10, backend="jax", routing="ecmp")
    read = run.load_reader("ecmp_onehot_share")
    flight.reset_dispatch_stats()
    assert read({}) is None                  # nothing launched yet
    execute_points([spec])                   # the CPU gathers
    assert read({}) == 0.0
    struct_cfg = megabatch._struct_cfg
    monkeypatch.setattr(megabatch, "_struct_cfg", lambda c: replace(
        struct_cfg(c), ecmp_onehot_bytes=1 << 30))
    flight.reset_dispatch_stats()
    execute_points([spec, spec.with_sim(routing="ar")])
    assert read({}) == pytest.approx(50.0)
    # a program without the counter reads nothing
    monkeypatch.setattr(flight, "dispatch_counts",
                        lambda: {"flow_slots_launched": 10})
    assert read({}) is None
