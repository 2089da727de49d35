"""The flight recorder inside the megabatch path: host spans on the
profiler's clock, the stage scopes and kernel names of the device
program, and the counters of one sweep (`repro.netsim.flight`)."""
import glob
import itertools
import re
import threading
from contextlib import nullcontext
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import execute_points
from repro.netsim import flight
from repro.netsim.jx import engine
from repro.netsim.jx.megabatch import FLOW_BUCKET_MIN, _bucket
from repro.scenarios import compile_scenario, get_scenario

# span -> the span that encloses it (None: outermost on its thread)
PARENT = {
    "repro.execute": None,
    "repro.scenario": "repro.execute",
    "repro.prep.flows": "repro.scenario",
    "repro.plan": "repro.execute",
    "repro.dispatch": None,                 # the prep worker thread
    "repro.prep.point": "repro.dispatch",
    "repro.prep.flow_arrays": "repro.prep.point",
    "repro.prep.timeline": "repro.prep.point",
    "repro.prep.ecmp_replay": "repro.prep.point",
    "repro.prep.widths": "repro.prep.point",
    "repro.launch": "repro.dispatch",
    "repro.finalize": "repro.execute",
    "repro.finalize.wait": "repro.finalize",
    "repro.finalize.unpack": "repro.finalize",
    "repro.distill": "repro.execute",
}
SCOPES = ("slot/segment", "slot/plane_split", "slot/route",
          "slot/host_load", "slot/access_scale", "slot/queue", "slot/nic",
          "slot/complete")
_uniq = itertools.count()


def _grid(slots=20):
    """ECMP and AR points of a small all-to-all: 992 flows each, not a
    power of two."""
    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=slots, backend="jax", routing="ecmp")
    return [spec, spec.with_sim(seed=3), spec.with_sim(routing="ar")]


def _giga_tiny(routing):
    """`giga_fabric_storage`'s structure (2 planes, storage fanout,
    random fabric kills) on 32 hosts."""
    base = get_scenario("giga_fabric_storage")
    w = replace(base.workloads[0], fanout=5)
    f = replace(base.faults[0], start_slot=6, count=2)
    return replace(base, topo=replace(base.topo, n_leaves=8, n_spines=4,
                                      hosts_per_leaf=4),
                   workloads=(w,), faults=(f,)).with_sim(
        slots=12, backend="jax", routing=routing)


def _traced_events(tmp_path):
    """Every `repro.*` event of the trace under `tmp_path`: (thread
    line, start, end, name, sweep)."""
    from jax.profiler import ProfileData

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = int(ev.start_ns)
                    out.append(((plane.name, li), s,
                                s + int(ev.duration_ns), ev.name,
                                dict(ev.stats).get("sweep")))
    return out


def test_sweep_spans_nest_carry_the_sweep_and_match_phases(tmp_path):
    points = _grid()
    execute_points(points)                  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    flights = [{}, {}]
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for fl in flights:
            execute_points(points, flight=fl)
    finally:
        jax.profiler.stop_trace()
    events = _traced_events(tmp_path)
    sweeps = [fl["sweep"] for fl in flights]
    assert sweeps[0] != sweeps[1]
    assert {e[4] for e in events} == set(sweeps)
    for fl in flights:
        mine = [e for e in events if e[4] == fl["sweep"]]
        assert {e[3] for e in mine} == set(PARENT)
        assert set(fl["phases"]) == set(PARENT)
        [outer] = [e for e in mine if e[3] == "repro.execute"]
        for line, s, e, name, _ in mine:
            assert outer[1] <= s and e <= outer[2], name
            enclosing = [o for o in mine if o[0] == line and o[3] != name
                         and o[1] <= s and e <= o[2]]
            inner = min(enclosing, key=lambda o: o[2] - o[1],
                        default=None)
            assert (inner[3] if inner else None) == PARENT[name], name
        # the host clock agrees with the profiler's within 1%, plus the
        # few microseconds between each annotation's edges and the clock
        # reads inside it
        for name, secs in fl["phases"].items():
            traced = [e - s for _, s, e, n, _ in mine if n == name]
            assert abs(secs * 1e9 - sum(traced)) <= \
                0.01 * sum(traced) + 20_000 * len(traced), name


def test_flow_slot_counters_count_bucket_padding():
    points = _grid(slots=20)
    fl = {}
    execute_points(points, flight=fl)
    F = len(compile_scenario(points[0]).flows)
    F_b = _bucket(F, FLOW_BUCKET_MIN)
    assert F & (F - 1) and F_b > F           # not a power of two
    c = fl["counters"]
    assert c["flow_slots_real"] == 20 * F * len(points)
    assert c["flow_slots_launched"] == 20 * F_b * len(points)
    assert 100 * (1 - c["flow_slots_real"] / c["flow_slots_launched"]) \
        == pytest.approx(100 * (F_b - F) / F_b)
    assert c["launch_bytes"] > 0
    assert "points" not in fl                # no amortized per-point wall


@pytest.mark.parametrize("contract", [True, False],
                         ids=["onehot", "gather"])
def test_ecmp_onehot_counter_counts_contracted_ecmp_flow_slots(
        monkeypatch, contract):
    from repro.netsim.jx import megabatch

    if contract:
        struct_cfg = megabatch._struct_cfg
        monkeypatch.setattr(megabatch, "_struct_cfg", lambda c: replace(
            struct_cfg(c), ecmp_onehot_bytes=1 << 30))
    points = _grid(slots=20)                 # two ECMP points, one AR
    F_b = _bucket(len(compile_scenario(points[0]).flows), FLOW_BUCKET_MIN)
    fl, pair = {}, {}
    execute_points(points, flight=fl)
    execute_points(points[2:], flight=pair)
    assert fl["counters"]["ecmp_onehot_flow_slots"] == \
        (20 * F_b * 2 if contract else 0)
    assert pair["counters"]["ecmp_onehot_flow_slots"] == 0


def test_pair_route_counter_counts_ar_and_war_flow_slots():
    """`pair_route_flow_slots` counts the launched flow-slots of rows
    that route by leaf pair: none of an ECMP launch, two rows of three
    in a mixed ar/war/ecmp launch."""
    ecmp = _grid(slots=20)[0]
    only, mixed = {}, {}
    execute_points([ecmp], flight=only)
    execute_points([ecmp.with_sim(routing=r) for r in ("ar", "war", "ecmp")],
                   flight=mixed)
    assert only["counters"]["pair_route_flow_slots"] == 0
    c = mixed["counters"]
    F_b = _bucket(len(compile_scenario(ecmp).flows), FLOW_BUCKET_MIN)
    assert c["flow_slots_launched"] == 3 * 20 * F_b
    assert c["pair_route_flow_slots"] == 2 * 20 * F_b


def test_compile_on_a_worker_thread_is_credited_to_the_caller():
    flight.watch_compiles()
    n = 5 + next(_uniq) + 7 * (threading.get_ident() % 97)
    x, y = jnp.ones((n, 3)), jnp.ones((n + 1, 3))
    done = []
    with flight.collect_dispatch() as caller:
        collectors = flight.current_collectors()

        def work():
            with flight.adopt_dispatch(collectors):
                jax.jit(lambda v: v * 3.0 - 1.0)(x).block_until_ready()
            done.append(True)

        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=120)
    assert not t.is_alive() and done
    c = caller.counts
    assert c.get("xla_compiles", 0) + c.get("cache_loads", 0) == 1
    assert c.get("xla_compile_s", 0) + c.get("cache_load_s", 0) > 0
    # a thread that adopted nothing credits only the process totals
    with flight.collect_dispatch() as bystander:
        t = threading.Thread(target=lambda: jax.jit(lambda v: v + 2.0)(
            y).block_until_ready())
        t.start()
        t.join(timeout=120)
    assert not t.is_alive()
    assert bystander.counts == {}


def _program(points, monkeypatch, pallas):
    """The megabatch program of `points` and its operands' shapes, traced
    afresh (a private jit cache)."""
    from repro.kernels import backend
    from repro.netsim.jx.megabatch import megabatch_programs

    monkeypatch.setenv("REPRO_NETSIM_PALLAS", "1" if pallas else "0")
    monkeypatch.setattr(backend, "pallas_interpret",
                        lambda override=None: True)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    (fn, args), = megabatch_programs(
        [compile_scenario(p) for p in points], n_devices=1)
    avals = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), jax.dtypes.canonicalize_dtype(np.asarray(a).dtype)),
        args)
    return fn, avals


@pytest.mark.parametrize("agg,kernels", [
    ("sparse", ("plane_split", "pair_fractions", "bottleneck",
                "queue_update", "nic_update")),
    ("dense", ("plane_split", "pair_fractions", "bottleneck",
               "bucket_load_bottleneck", "queue_update", "nic_update")),
])
def test_lowered_program_carries_stage_scopes_and_kernel_names(
        monkeypatch, agg, kernels):
    monkeypatch.setenv("REPRO_JX_AGG", agg)
    fn, avals = _program([_giga_tiny("ecmp"), _giga_tiny("ar")],
                         monkeypatch, pallas=True)
    text = fn.lower(*avals).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope + "/" in text, scope
    for kernel in kernels:
        assert f"/{kernel}/" in text, kernel


_METADATA = re.compile(r", metadata=\{[^}]*\}")
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


_NAME = re.compile(r"%[\w.\-]+")


def _ops(text):
    """A compiled program's text without its debug metadata (the per-op
    `metadata={...}` and the source-location tables), each instruction
    and computation renamed by its order of appearance: the numbering
    of XLA's names follows the op names that scopes lengthen."""
    names = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%{len(names)}")
    return [_NAME.sub(rename, line)
            for line in _METADATA.sub("", text).splitlines()
            if not (line[:1].isdigit() or line in _DEBUG_TABLES)]


@pytest.fixture
def no_compile_cache():
    """The persistent compilation cache off: its key leaves op metadata
    out, so the second program would load the first one's executable."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_stage_scopes_leave_the_compiled_program_unchanged(
        monkeypatch, no_compile_cache):
    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    points = [_giga_tiny("ecmp"), _giga_tiny("ar")]

    def compiled_text():
        fn, avals = _program(points, monkeypatch, pallas=False)
        return fn.lower(*avals).compile().as_text()

    scoped = compiled_text()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: nullcontext())
        plain = compiled_text()
    assert "slot/route" in scoped and "slot/route" not in plain
    assert _ops(scoped) == _ops(plain)
