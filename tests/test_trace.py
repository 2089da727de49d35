"""HFT trace capture (§5.3): backend parity, megabatch fusion with
tracing on, program identity with tracing off, exports, and the fig12
§5.2 acceptance signature.

Unique `sim.slots` values (137, 91, 73) keep jit program fingerprints
local to this file regardless of suite order.
"""
import json

import jax
import numpy as np
import pytest

from repro.core.telemetry import bw_histogram, classify_histogram, \
    find_stragglers
from repro.experiments import ResultSet, execute_points
from repro.netsim.jx import dispatch_stats, reset_dispatch_stats
from repro.netsim.jx.engine import run_compiled
from repro.scenarios import compile_scenario, get_scenario
from repro.scenarios.runner import run_point
from repro.trace import (TRACE_FIELDS, TraceSpec, trace_summary,
                         trace_to_npz, trace_to_perfetto)

TOL = 1e-5


def _fig12(slots, **trace_kw):
    return get_scenario("fig12_plane_flap").with_sim(
        slots=slots, trace=TraceSpec(enabled=True, **trace_kw))


def _assert_traces_close(a, b, where=""):
    assert set(a) == set(b), where
    for k in a:
        x = np.asarray(a[k], np.float64)
        y = np.asarray(b[k], np.float64)
        assert x.shape == y.shape, f"{where} {k}: {x.shape} vs {y.shape}"
        assert np.abs(x - y).max() < TOL, f"{where} {k}"


def test_trace_spec_validation():
    with pytest.raises(ValueError):
        TraceSpec(enabled=True, every=0).validate()
    with pytest.raises(ValueError):
        TraceSpec(fields=("host_bw", "nope")).validate()
    with pytest.raises(ValueError):
        TraceSpec(enabled=True, fields=()).validate()
    with pytest.raises(ValueError):
        get_scenario("fig12_plane_flap").with_sim(
            trace=TraceSpec(enabled=True, every=-3)).validate()
    assert TraceSpec(fields=("queue", "host_bw")).active_fields() == \
        ("host_bw", "queue")        # canonical capture order


def test_trace_numpy_jax_parity_fig12():
    """Every trace field matches 1e-5 (x64) between the numpy loop and
    the jx scan on the fig12 flap scenario."""
    spec = _fig12(slots=137)
    rn = compile_scenario(spec).run()
    with jax.enable_x64(True):
        rj = run_compiled(compile_scenario(spec))
    assert set(rn.trace) == set(TRACE_FIELDS) | {"slot"}
    _assert_traces_close(rn.trace, rj.trace, "fig12")


def test_trace_decimation_and_field_subset():
    """`every=7` records slots 0,7,14,... on both backends; a fields
    subset captures only those fields."""
    spec = _fig12(slots=137, every=7, fields=("host_bw", "queue"))
    rn = compile_scenario(spec).run()
    with jax.enable_x64(True):
        rj = run_compiled(compile_scenario(spec))
    expect = np.arange(0, 137, 7)
    assert np.array_equal(rn.trace["slot"], expect)
    assert set(rn.trace) == {"slot", "host_bw", "queue"}
    assert rn.trace["host_bw"].shape[0] == expect.shape[0]
    _assert_traces_close(rn.trace, rj.trace, "decimated")


def test_trace_off_no_capture_and_program_reuse():
    """Tracing off: `res.trace` is None on both backends, and the jx
    program is byte-for-byte the pre-trace program — enabling tracing
    compiles a *different* program, after which the trace-off grid still
    reuses its original compile (0 new compiles)."""
    base = get_scenario("flap_during_incast").with_sim(slots=91)
    points_off = [base.with_sim(routing=r) for r in ("ar", "ecmp")]
    points_on = [p.with_sim(trace=TraceSpec(enabled=True))
                 for p in points_off]
    assert compile_scenario(points_off[0]).run().trace is None

    reset_dispatch_stats()
    res_off = execute_points(points_off, backend="jax")
    assert dispatch_stats() == {"dispatches": 1, "compiles": 1}
    assert all(np.isnan(m.bimodal_frac) for m in res_off)
    assert all(m.hft_transient_drops == -1 for m in res_off)

    reset_dispatch_stats()
    execute_points(points_on, backend="jax")
    assert dispatch_stats() == {"dispatches": 1, "compiles": 1}

    # back to trace-off: the original fused program serves the grid warm
    reset_dispatch_stats()
    execute_points(points_off, backend="jax")
    assert dispatch_stats() == {"dispatches": 1, "compiles": 0}


def test_megabatch_traced_one_compile_per_bucket_and_trace_parity():
    """A traced multi-scenario grid still fuses to one compile per flow
    bucket, and every point's (bucket-padded, lane-sorted) raw trace
    matches the single-point jx reference — pinning the flow-axis strip
    in `finalize_group`."""
    from repro.netsim.jx.megabatch import (dispatch_megabatch,
                                           finalize_group)

    ts = TraceSpec(enabled=True)
    points = [get_scenario(s).with_sim(slots=73, routing=r, trace=ts)
              for s in ("flap_during_incast", "staggered_incast_bursts")
              for r in ("ar", "ecmp")]
    with jax.enable_x64(True):
        compiled = [compile_scenario(p) for p in points]
        reset_dispatch_stats()
        res = {}
        for idxs, handle in dispatch_megabatch(compiled):
            for i, r in zip(idxs, finalize_group(handle)):
                res[i] = r
        stats = dispatch_stats()
        assert stats["dispatches"] == 2, stats   # two flow buckets
        assert stats["compiles"] == 2, stats
        for i, (p, c) in enumerate(zip(points, compiled)):
            ref = run_compiled(compile_scenario(p))
            _assert_traces_close(res[i].trace, ref.trace, p.name)
            assert res[i].trace["ecn"].shape[1] == len(c.flows)


def test_fig12_acceptance_signature():
    """§5.2 on the full fig12 run: the flapped (host 0, plane 1) port is
    bi-modal healthy-blocked, the surviving ports are line-rate, host 0
    is the named straggler, and a quarter of active ports are bi-modal."""
    spec = _fig12(slots=600)
    res = compile_scenario(spec).run()
    cap = spec.topo.access_cap
    port = res.trace["host_bw"] / cap
    assert classify_histogram(bw_histogram(port[:, 0, 1])) == \
        "healthy-blocked"
    for plane in (0, 2, 3):
        assert classify_histogram(bw_histogram(port[:, 0, plane])) == \
            "line-rate"
    host = res.trace["host_bw"].sum(2) / (cap * spec.topo.n_planes)
    assert find_stragglers(host.T) == [0]

    summ = trace_summary(res.trace, cap, spec.topo.n_planes)
    assert summ["straggler_ranks"] == (0,)
    assert summ["bimodal_frac"] == pytest.approx(0.25)
    assert summ["hft_transient_drops"] >= 0

    m = run_point(spec)
    assert m.straggler_ranks == (0,)
    assert m.bimodal_frac == pytest.approx(0.25)
    assert m.extra["port_classes"]["healthy-blocked"] == 1


def test_trace_exports_roundtrip(tmp_path):
    spec = _fig12(slots=137)
    res = compile_scenario(spec).run()
    npz = tmp_path / "t.npz"
    pft = tmp_path / "t.json"
    trace_to_npz(str(npz), res.trace, slot_us=spec.sim.slot_us)
    trace_to_perfetto(str(pft), res.trace, slot_us=spec.sim.slot_us,
                      label="fig12")
    z = np.load(str(npz))
    assert np.array_equal(z["host_bw"], res.trace["host_bw"])
    assert float(z["slot_us"]) == spec.sim.slot_us
    doc = json.loads(pft.read_text())
    events = doc["traceEvents"]
    assert events and all("ts" in e for e in events)
    # the plane-1 access kill at slot 50 shows up as a failover instant
    instants = [e for e in events if e["ph"] == "i"]
    assert any("plane1 failover" in e["name"] for e in instants)
    # counter tracks exist for every host and plane
    names = {e["name"] for e in events}
    assert "host0.goodput" in names and "plane1.util" in names


def test_trace_metrics_in_resultset_and_backfill():
    """Trace-derived columns ride ResultSet JSON/CSV round-trips, and
    serializations written before the columns existed still load (the
    defaults are backfilled)."""
    m = run_point(_fig12(slots=137))
    rs = ResultSet()
    rs.append(m)
    rt = ResultSet.from_json(rs.to_json()).to_metrics()[0]
    assert rt.straggler_ranks == m.straggler_ranks
    assert rt.bimodal_frac == pytest.approx(m.bimodal_frac)
    assert rt.hft_transient_drops == m.hft_transient_drops
    rc = ResultSet.from_csv(rs.to_csv()).to_metrics()[0]
    assert rc.straggler_ranks == m.straggler_ranks

    # pre-trace JSON: new columns absent entirely
    d = json.loads(rs.to_json())
    for col in ("hft_transient_drops", "bimodal_frac", "straggler_ranks"):
        del d["columns"][col]
    old = ResultSet.from_json(json.dumps(d)).to_metrics()[0]
    assert old.hft_transient_drops == -1
    assert np.isnan(old.bimodal_frac)
    assert old.straggler_ranks == ()


def test_flight_recorder_attached():
    from repro.experiments import Axis, Experiment, run_experiment

    exp = Experiment(name="test_trace.flight", base="fig12_plane_flap",
                     axes=Axis("sim.slots", (137,)))
    rs = run_experiment(exp, backend="numpy")
    fl = rs.flight
    assert fl["cache_misses"] == 1
    [ex] = fl["executions"]
    assert ex["backend"] == "numpy" and ex["n_points"] == 1
    assert ex["points"][0]["wall_s"] > 0
    assert ResultSet.from_json(rs.to_json()).flight == fl

    rs2 = run_experiment(exp, backend="jax")
    [ex2] = rs2.flight["executions"]
    assert ex2["mode"] == "megabatch"
    assert "dispatches" in ex2["dispatch_stats"]
