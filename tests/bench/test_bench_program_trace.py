"""The program's spans and counters beside the benchmark's reduction:
the existing readers ignore them, the new readers and the trace tool
(`program_trace.py`) read them."""
import glob

import pytest

import program_trace
import run
import xplane
from test_bench_xplane import GATHER, JSQ, LOOP, NIC, SCATTER, SPLIT, _point

READERS = ("host_prep_s_per_sweep", "window_compiles",
           "device_ns_per_flow_slot", "pallas_roofline", "scatter_share",
           "device_idle_share", "peak_hbm_mb", "finalize_s_per_sweep")
DEVICE = {"/device:TPU:0": [(90, 600, LOOP), (100, 200, GATHER),
                            (150, 250, NIC), (400, 450, SCATTER),
                            (500, 560, SPLIT), (900, 1000, GATHER)]}
BENCH_SPANS = [(50, 700, "bench.sweep"), (60, 95, "bench.compile_scenario"),
               (600, 690, "bench.finalize_group")]
# the program's own spans over the same window, as the profiler
# records them beside the benchmark's
PROGRAM_SPANS = [(55, 698, "repro.execute"), (58, 97, "repro.scenario"),
                 (62, 90, "repro.prep.flows"), (601, 650, "repro.finalize"),
                 (601, 640, "repro.finalize.wait"), (650, 680, "repro.distill")]


def _ctx(reduced):
    return {"trace": reduced, "sweeps": 1, "flow_slots": 6000,
            "compiles": 0, "memory_peak_bytes": 123_000_000,
            "device_kind": "TPU v5 lite", "bench_dir": run.BENCH,
            "traced_points": [_point("ar", "spx")]}


def test_existing_readers_ignore_program_spans():
    without = _ctx(xplane.reduce(DEVICE, BENCH_SPANS))
    with_program = _ctx(xplane.reduce(DEVICE, BENCH_SPANS + PROGRAM_SPANS))
    for name in READERS:
        read = run.load_reader(name)
        assert read(with_program) == read(without), name
        assert read(without) is not None, name


def test_idle_split_over_two_host_spans():
    # one idle stretch, 250..400, covered by a prep span to 300, then a
    # launch span from 320 (inside the sweep span to the end); the
    # midpoint rule would give all 150 ns to the launch span
    ops = [(100, 250), (400, 500)]
    spans = [(0, 600, "repro.execute", 1), (240, 300, "repro.prep.point", 1),
             (320, 420, "repro.launch", 1)]
    idle = program_trace.split_idle(ops, spans, (100, 500))
    assert idle == {"repro.prep.point": 50, "repro.execute": 20,
                    "repro.launch": 80}
    # what no span covers is named as such
    assert program_trace.split_idle(ops, spans[1:], (100, 500)) == {
        "repro.prep.point": 50, program_trace.OUTSIDE: 20,
        "repro.launch": 80}


def test_stage_time_and_kernel_names_by_instruction_name():
    nic = NIC.replace("%closed_call.1 ", "%nic_update.4 ")
    split = SPLIT.replace("%closed_call.2 ", "%plane_split.7 ")
    path = "jit(body)/while/body/slot/{}"
    names = {"nic_update.4": path.format("nic/nic_update/pallas_call"),
             "fusion.121": path.format("route/gather"),
             "plane_split.7": path.format("plane_split/plane_split"),
             "closed_call.3": path.format("route/pair_fractions")}
    ops = [(0, 10, nic), (10, 40, GATHER), (40, 45, SCATTER),
           (45, 60, split), (60, 70, JSQ), (0, 60, LOOP)]
    # the scatter's instruction is in no scope
    stages, total, kernels, unscoped = program_trace.stage_ns(
        ops, (0, 100), names)
    assert stages == {"nic": 10, "route": 40, "plane_split": 15}
    assert total == 70
    assert unscoped == {("fusion.114 scatter f32[8192]", None): 5}
    # an unnamed kernel instruction (as at the parent) names nothing
    assert kernels == {("_nic_update_kernel", "nic_update"): 10,
                       ("_plane_split_kernel", "plane_split"): 15,
                       ("_pair_score_kernel", None): 10}


def test_op_names_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("slot/route"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("slot/queue"):
            return y.sum()

    text = jax.jit(f).lower(jnp.ones(64)).compile().as_text()
    paths = program_trace.op_names(text).values()
    assert any("slot/route/" in p for p in paths)
    assert any("slot/queue/" in p for p in paths)


# a scatter-add as XLA's TPU compiler leaves it: the fusions around it
# and the scatter itself carry no op name, its operand carries the name
# of the stage that computed it, its reduction the name of its own stage
FUSED = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_1.3 (scatter-add.2: f32[], scatter-add.3: f32[]) -> f32[] {
  %scatter-add.2 = f32[]{:T(128)} parameter(0), metadata={op_name="scatter-add"}
  %scatter-add.3 = f32[]{:T(128)} parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.21 = f32[]{:T(128)} add(%scatter-add.2, %scatter-add.3), metadata={op_name="slot/route/add" stack_frame_id=39}
}

%fused_computation.8 (param_0.478: f32[8], param_1.561: s32[16], param_2.481: f32[16]) -> f32[8] {
  %param_0.478 = f32[8]{0} parameter(0)
  %param_1.561 = s32[16]{0} parameter(1)
  %param_2.481 = f32[16]{0} parameter(2)
  %transpose.148 = f32[16]{0} transpose(%param_2.481), dimensions={0}, metadata={op_name="jit(step)/while/body/slot/plane_split/select_n"}
  ROOT %scatter.25 = f32[8]{0} scatter(%param_0.478, %param_1.561, %transpose.148), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_1.3
}

%fused_computation.136 (param_0.479: f32[8], param_1.562: s32[16], param_2.482: f32[16]) -> f32[8] {
  %param_0.479 = f32[8]{0} parameter(0)
  %param_1.562 = s32[16]{0} parameter(1)
  %param_2.482 = f32[16]{0} parameter(2)
  ROOT %fusion.99 = f32[8]{0} fusion(%param_0.479, %param_1.562, %param_2.482), kind=kCustom, calls=%fused_computation.8
}

%fused_computation.5 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  ROOT %multiply.6 = f32[8]{0} multiply(f32[8]{0} %param_0.3, f32[8]{0} %param_0.3), metadata={op_name="jit(step)/while/body/slot/nic/mul"}
}

ENTRY %main.9 (Arg_0.1: f32[8], Arg_1.2: s32[16], Arg_2.3: f32[16]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %Arg_1.2 = s32[16]{0} parameter(1)
  %Arg_2.3 = f32[16]{0} parameter(2)
  %fusion.114 = f32[8]{0} fusion(%Arg_0.1, %Arg_1.2, %Arg_2.3), kind=kLoop, calls=%fused_computation.136
  %copy.94 = f32[8]{0} copy(%fusion.114)
  ROOT %fusion.7 = f32[8]{0} fusion(%copy.94), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/while/body/slot/queue/mul"}
}
"""


def test_op_names_give_a_fusion_its_scatter_scope():
    names = program_trace.op_names(FUSED)
    # a fusion without an op name takes its scatter's reduction's, not
    # its operand's
    assert program_trace.stage_of(names["fusion.114"]) == "route"
    assert program_trace.stage_of(names["scatter.25"]) == "route"
    # a fusion with its own op name keeps it
    assert names["fusion.7"].endswith("slot/queue/mul")
    assert program_trace.stage_of(names["multiply.6"]) == "nic"
    # a layout copy has none
    assert "copy.94" not in names
    stages, total, _, unscoped = program_trace.stage_ns(
        [(0, 5, SCATTER)], (0, 10), names)
    assert stages == {"route": 5} and total == 5 and unscoped == {}


def test_program_counters_feed_the_new_readers():
    from repro.experiments import execute_points
    from repro.netsim import flight
    from repro.scenarios import compile_scenario, get_scenario

    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=10, backend="jax", routing="ecmp")
    flight.reset_dispatch_stats()
    execute_points([spec])
    F = len(compile_scenario(spec).flows)
    F_b = 1 << (F - 1).bit_length()
    assert F != F_b
    pad = run.load_reader("pad_flow_share")({})
    assert pad == pytest.approx(100.0 * (F_b - F) / F_b)
    c = flight.dispatch_counts()
    compile_s = c.get("xla_compile_s", 0.0) + c.get("cache_load_s", 0.0)
    read = run.load_reader("setup_compile_s")
    assert read({"compiles": 0}) == compile_s
    # whole-process totals are set-up's only if the window compiled
    # nothing
    assert read({"compiles": 1}) is None


def test_program_spans_reach_the_trace_and_not_the_benchmark_spans(
        tmp_path):
    import jax

    from repro.experiments import execute_points
    from repro.scenarios import get_scenario

    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=10, backend="jax")
    execute_points([spec])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    fl = {}
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        execute_points([spec], flight=fl)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = program_trace.program_spans(path[0])
    assert {n for _, _, n, _ in spans} == set(fl["phases"])
    assert {sweep for *_, sweep in spans} == {fl["sweep"]}
    per, n = program_trace.per_sweep(spans, (0, 2 ** 62))
    assert n == 1
    assert per["repro.execute"] == pytest.approx(
        fl["phases"]["repro.execute"], rel=0.01)
    # the benchmark's own reduction reads `bench.*` spans only
    _, bench_spans = xplane.read_planes(path[0])
    assert bench_spans == []
