"""Trace reduction and the kernel byte model, on synthetic inputs."""
import pytest

import xplane
from kernel_bytes import per_slot
from reference import Point

T = "{1,0:T(8,128)}"
NIC = ("%closed_call.1 = (f32[64,2]" + T + ", f32[64,2]" + T + ", f32[64,2]"
       + T + ", f32[64,2]" + T + ") custom-call(f32[64,2]" + T + " %a, "
       "f32[64,2]" + T + " %b, f32[64,2]" + T + " %c, f32[64,1]" + T
       + " %d), custom_call_target=\"tpu_custom_call\", "
       "frontend_attributes={kernel_metadata={}}")
SPLIT = ("%closed_call.2 = f32[64,2]" + T + " custom-call(f32[64,2]" + T
         + " %a, f32[64,2]" + T + " %b, f32[64,1]" + T + " %c), "
         "custom_call_target=\"tpu_custom_call\"")
JSQ = ("%closed_call.3 = f32[4,64,8]" + T + " custom-call(f32[4,64,8]" + T
       + " %a, f32[4,64,8]" + T + " %b, f32[4,64,8]" + T + " %c), "
       "custom_call_target=\"tpu_custom_call\"")
SCATTER = ("%fusion.114 = f32[8192]{0:T(1024)} fusion(s32[262144]{0} %i, "
           "f32[262144]{0} %v, f32[]{:T(128)} %z), kind=kCustom, "
           "calls=%fused_computation.117")
GATHER = ("%fusion.121 = f32[262144]{0:T(1024)} fusion(f32[1,2,16,256]"
          "{3,2,1,0} %t, s32[262144]{0} %i), kind=kCustom")
LOOP = ("%while.7 = (s32[]{:T(128)}, f32[1,2,256,16]{3,2,1,0}) "
        "while((s32[]{:T(128)}, f32[1,2,256,16]{3,2,1,0}) %tuple), "
        "condition=%c, body=%b")


def test_union_merges_overlaps():
    assert xplane.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert xplane.union_ns([]) == 0


def test_parse_op():
    name, opcode, res, ops = xplane.parse_op(SCATTER)
    assert (name, opcode) == ("fusion.114", "fusion")
    assert res == [("f32", 8192, (8192,))]
    assert [n for _, n, _ in ops] == [262144, 262144, 1]
    assert xplane.parse_op(LOOP)[1] == "while"


@pytest.mark.parametrize("text,kernel,scatter", [
    (NIC, "_nic_update_kernel", False),
    (SPLIT, "_plane_split_kernel", False),
    (JSQ, "_pair_score_kernel", False),
    (SCATTER, None, True),
    (GATHER, None, False),
    (LOOP, None, False),
])
def test_op_classes(text, kernel, scatter):
    assert xplane.kernel_of(text) == kernel
    assert xplane.is_scatter(text) is scatter


def test_reduce_synthetic_trace():
    dev = {"/device:TPU:0": [
        (90, 600, LOOP),                   # container: busy, not listed
        (100, 200, GATHER),
        (150, 250, NIC),                   # overlaps the gather
        (400, 450, SCATTER),
        (500, 560, SPLIT),
        (900, 1000, GATHER),               # after the window
    ]}
    spans = [(50, 700, "bench.sweep"),
             (60, 95, "bench.compile_scenario"),
             (600, 690, "bench.finalize_group")]
    r = xplane.reduce(dev, spans)
    assert r["window_ns"] == 650
    assert r["busy_ns"] == 510                       # the loop, 90..600
    assert r["pallas_ns"] == {"_nic_update_kernel": 100,
                              "_plane_split_kernel": 60}
    assert r["scatter_ns"] == 50
    top = dict(r["top_ops"])
    assert top["fusion.121 fusion f32[262144]"] == 100
    assert not any(k.startswith("while") for k in top)
    assert r["spans_ns"]["bench.finalize_group"] == 90
    idle = dict(r["idle_by_span"])
    # gaps: [50, 90] (under compile_scenario), [600, 700] (finalize to
    # 690, the sweep after it)
    assert idle == {"bench.compile_scenario": 40,
                    "bench.finalize_group": 100}


def test_reduce_averages_busy_over_devices():
    dev = {"/device:TPU:0": [(0, 100, GATHER)],
           "/device:TPU:1": [(0, 50, GATHER)]}
    r = xplane.reduce(dev, [(0, 100, "bench.sweep")])
    assert r["busy_ns"] == 75 and r["devices"] == 2


def test_reduce_without_sweeps_is_empty():
    assert xplane.reduce({}, []) == {}


def test_read_planes_finds_host_spans(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # as the benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.sweep"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    _, spans = xplane.read_planes(path[0])
    assert [n for _, _, n in spans] == ["bench.sweep"]


def _point(routing, nic):
    cfg = {"topology": {"n_leaves": 2, "n_spines": 2, "hosts_per_leaf": 2,
                        "n_planes": 2},
           "tenant": {"offset": 0, "n_hosts": 3},
           "workload": {"kind": "incast", "sinks": 1},
           "sim": {"slots": 10}}
    return Point(cfg, routing, nic, None, None, 0, 0)


@pytest.mark.parametrize("routing,nic,dense,expect", [
    # F=2 flows, P=2, L=2, S=2, H=4 (PLS = 8):
    # plane split 4*4 + 4 + 2*4 + 4*4 = 44; host ports 2*3*4*8 = 192,
    # fabric scales 2*3*4*8 = 192; queues 9*4*8 = 288;
    # JSQ split 4*4*8 + 4*16 = 192; spx NIC 5*4*4 = 80
    ("ar", "spx", False, {"_plane_split_kernel": 44,
                          "_bottleneck_kernel": 384,
                          "_queue_update_kernel": 288,
                          "_pair_score_kernel": 192,
                          "_nic_update_kernel": 80}),
    # dense ECMP: fused bucket sum 2*4*4 + 2*3*4*8 = 224; dcqcn 7*4*4
    ("ecmp", "dcqcn", True, {"_plane_split_kernel": 44,
                             "_bottleneck_kernel": 192,
                             "_queue_update_kernel": 288,
                             "_load_bottleneck_kernel": 224,
                             "_nic_update_kernel": 112}),
])
def test_kernel_bytes_hand_counted(routing, nic, dense, expect):
    assert per_slot(_point(routing, nic), dense) == expect
