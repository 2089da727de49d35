"""The adaptive-routing readers: `pair_route_share` against the program's
launch counters, and `pair_score_roofline` against a reduced trace
holding the JSQ scoring kernel.  Each reads nothing where what it reads
is absent."""
import pytest

import run
import xplane
from test_bench_xplane import JSQ, NIC, _point


def test_pair_route_share_reads_the_pair_routed_share(monkeypatch):
    from repro.experiments import execute_points
    from repro.netsim import flight
    from repro.scenarios import get_scenario

    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=10, backend="jax", routing="ecmp")
    read = run.load_reader("pair_route_share")
    flight.reset_dispatch_stats()
    assert read({}) is None                  # nothing launched yet
    execute_points([spec])
    assert read({}) == 0.0
    flight.reset_dispatch_stats()
    execute_points([spec.with_sim(routing=r) for r in ("ar", "war", "ecmp")])
    assert read({}) == pytest.approx(200.0 / 3)
    # a program without the counter reads nothing
    monkeypatch.setattr(flight, "dispatch_counts",
                        lambda: {"flow_slots_launched": 10})
    assert read({}) is None


def _ctx(device_ops, points, kind="TPU v5 lite"):
    trace = xplane.reduce(device_ops, [(0, 2000, "bench.sweep")])
    return {"trace": trace, "device_kind": kind,
            "bench_dir": run.BENCH, "traced_points": points}


def test_pair_score_roofline_reads_the_scoring_kernel_alone():
    read = run.load_reader("pair_score_roofline")
    points = [_point("war", "spx"), _point("ar", "dcqcn"),
              _point("ecmp", "spx")]
    # 1,000 ns of JSQ scoring beside 500 ns of NIC update
    ctx = _ctx({"/device:TPU:0": [(0, 1000, JSQ), (1000, 1500, NIC)]},
               points)
    # P=2, L=2, S=2 (PLS = 8, PLLS = 16), 10 slots, two pair-routed
    # points: (4*4*8 + 4*16) * 10 * 2 bytes over 819 GB/s in 1,000 ns
    want = 100.0 * (192 * 10 * 2 / 819e9) / 1e-6
    assert read(ctx) == pytest.approx(want)
    # the kernel did not run: nothing to read
    assert read(_ctx({"/device:TPU:0": [(0, 500, NIC)]}, points)) is None
    assert read({"trace": None}) is None
    with pytest.raises(KeyError):
        read(_ctx({"/device:TPU:0": [(0, 1000, JSQ)]}, points,
                  kind="TPU v9"))
