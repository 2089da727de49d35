"""The engine's Pallas kernels and its megabatch program compile for a
TPU v5e that is described, not attached.

Interpret mode (`test_kernels_interpret.py`) cannot see what Mosaic
refuses: i1 reductions, blocks off the (8, 128) tiling, programs that
do not fit the chip.  Here each of the six engine kernels is lowered
through Mosaic at the shapes of `flap_during_incast` (the acceptance
grid) and `giga_fabric_storage` (4,096 hosts, sparse aggregation), and
one whole megabatch program is compiled with the kernels on.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, so
under pytest-xdist only the worker given this file loads it.
"""
import functools
import os
import re
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import jsq_route, link_load, plb_select, queue_ecn

# the v5e's HBM
DEVICE_BYTES = 16 * 10**9
SCENARIOS = ("flap_during_incast", "giga_fabric_storage")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@functools.lru_cache(maxsize=None)
def _dims(name):
    """Engine operand sizes of a registry scenario: planes, leaves,
    spines, hosts, and the megabatch flow bucket."""
    from repro.netsim.jx.megabatch import FLOW_BUCKET_MIN, _bucket
    from repro.scenarios import compile_scenario, get_scenario

    spec = get_scenario(name)
    t = spec.topo
    F = _bucket(len(compile_scenario(spec).flows), FLOW_BUCKET_MIN)
    return dict(P=t.n_planes, L=t.n_leaves, S=t.n_spines, H=t.n_hosts,
                F=F)


def _compile(fn, sharding, *shapes):
    """Lower `fn` for the described chip and return the number of Mosaic
    kernels (`tpu_custom_call`) in the compiled program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


def _compile_program(fn, args, sharding):
    """Compile a megabatch program for the described chip from the
    shapes of its operands."""
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), jax.dtypes.canonicalize_dtype(np.asarray(a).dtype),
            sharding=sharding), args)
    with warnings.catch_warnings():
        # the scan rewrites the donated carry; not every leaf aliases
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn.lower(*avals).compile()


f32, b1 = jnp.float32, jnp.bool_
_NIC_KW = dict(base_rtt_us=4.0, slot_us=10.0, ecn_thresh=4.0,
               target_rtt_us=12.0, min_rate=0.01, md=0.7, ai=0.08,
               rtt_gain=0.15, dcqcn_ai=0.01, alpha_g=0.0625)


def _plane_split(d):
    def fn(rate, elig, demand):
        return [plb_select.plane_split(rate, elig, demand, mode=m,
                                       min_rate=0.01, use_pallas=True,
                                       interpret=False)
                for m in ("spx", "dcqcn", "agg", "swlb")]
    fp = (d["F"], d["P"])
    return fn, [(fp, f32), (fp, b1), ((d["F"],), f32)], 4


def _nic_update(d):
    def fn(qmean, rate, alpha, esr):
        return [queue_ecn.nic_update(qmean, rate, alpha, esr, mode=m,
                                     use_pallas=True, interpret=False,
                                     **_NIC_KW)
                for m in ("spx", "dcqcn", "agg")]
    fp = (d["F"], d["P"])
    return fn, [(fp, f32)] * 3 + [((d["F"], 1), b1)], 3


def _pair_fractions(d):
    def fn(q, cap, w):
        return jsq_route.pair_fractions(q, cap, w, use_pallas=True,
                                        interpret=False)
    s = (d["P"], d["L"], d["L"], d["S"])
    return fn, [(s, f32)] * 3, 1


def _bucket_load_bottleneck(d):
    def fn(g, cap):
        return link_load.bucket_load_bottleneck(
            g, cap, ordered=False, use_pallas=True, interpret=False)
    rows = 2 * d["L"] * d["S"]
    return fn, [((d["P"], rows, 16), f32), ((d["P"], rows), f32)], 1


def _bottleneck(d):
    def fn(cap, load, acc, acc_load):
        return (link_load.bottleneck(cap, load, use_pallas=True,
                                     interpret=False),
                link_load.bottleneck(acc, acc_load, use_pallas=True,
                                     interpret=False))
    links, hosts = (d["P"], d["L"], d["S"]), (d["H"], d["P"])
    return fn, [(links, f32)] * 2 + [(hosts, f32)] * 2, 2


def _queue_update(d):
    def fn(q, load, cap):
        return queue_ecn.queue_update(q, load, cap, q_cap=64.0,
                                      use_pallas=True, interpret=False)
    return fn, [((d["P"], d["L"], d["S"]), f32)] * 3, 1


KERNELS = {"plane_split": _plane_split, "nic_update": _nic_update,
           "pair_fractions": _pair_fractions,
           "bucket_load_bottleneck": _bucket_load_bottleneck,
           "bottleneck": _bottleneck, "queue_update": _queue_update}


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_engine_kernel_lowers_through_mosaic(one_chip, kernel, scenario):
    fn, shapes, n_calls = KERNELS[kernel](_dims(scenario))
    assert _compile(fn, one_chip, *shapes) >= n_calls


def test_megabatch_program_compiles_with_kernels(one_chip, monkeypatch):
    """A routing x NIC grid of `flap_during_incast` as one megabatch
    program, the Pallas kernels compiled (not interpreted) inside its
    scan, and the program fits the chip."""
    from repro.kernels import backend
    from repro.netsim.jx import engine
    from repro.netsim.jx.megabatch import megabatch_programs
    from repro.scenarios import compile_scenario, get_scenario

    monkeypatch.setenv("REPRO_NETSIM_PALLAS", "1")
    monkeypatch.setattr(backend, "pallas_interpret",
                        lambda override=None: False)
    # a private jit cache: the program traced here must not serve a
    # later CPU run of the same config
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    base = get_scenario("flap_during_incast")
    points = [compile_scenario(base.with_sim(routing=r, nic=n,
                                             backend="jax"))
              for r in ("ar", "war", "ecmp") for n in ("spx", "dcqcn")]
    (fn, args), = megabatch_programs(points, n_devices=1)
    compiled = _compile_program(fn, args, one_chip)
    assert compiled.as_text().count("tpu_custom_call") > 0
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < DEVICE_BYTES


_DEF = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]")
_GATHER = re.compile(r" gather\((%[\w.\-]+), (%[\w.\-]+)\)")


def _point_gathers(text, rows):
    """Gathers of the compiled program whose index operand holds `rows`
    index vectors of three or more components: per-flow point lookups
    of a (plane, leaf, spine) table, batch coordinate included."""
    dims = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if m:
            dims[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    out = []
    for line in text.splitlines():
        m = _GATHER.search(line)
        idx = dims.get(m.group(2)) if m else None
        if idx and idx[-1] >= 3 and np.prod(idx[:-1]) == rows:
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("scenario,contract", [
    ("giga_fabric_storage", True), ("flap_during_incast", False)])
def test_ecmp_program_contracts_its_link_lookups(one_chip, monkeypatch,
                                                 scenario, contract):
    """An ECMP point as one megabatch program, compiled for the chip with
    the link lookups it selects there.  The giga point's one-hot
    contraction compiles, fits, and leaves no per-flow point gather of a
    link table; a program that keeps the gathers holds four of them."""
    from repro.netsim.jx import engine, megabatch
    from repro.scenarios import compile_scenario, get_scenario

    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    budget = engine.ecmp_onehot_budget("tpu" if contract else "cpu")
    struct_cfg = megabatch._struct_cfg
    monkeypatch.setattr(megabatch, "_struct_cfg", lambda c: replace(
        struct_cfg(c), ecmp_onehot_bytes=budget))
    compiled_spec = compile_scenario(get_scenario(scenario).with_sim(
        routing="ecmp", backend="jax"))
    (fn, args), = megabatch.megabatch_programs([compiled_spec],
                                               n_devices=1)
    F, P = args[2].src.shape[1], _dims(scenario)["P"]
    assert engine.ecmp_onehot(megabatch._struct_cfg(compiled_spec),
                              F) == contract
    compiled = _compile_program(fn, args, one_chip)
    assert len(_point_gathers(compiled.as_text(), F * P)) == \
        (0 if contract else 4)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < DEVICE_BYTES


_MXU_OP = re.compile(r" (?:dot|convolution)\(")


def test_war_program_keeps_its_pair_loads_in_float32(one_chip, monkeypatch):
    """The giga point under weighted AR as one megabatch program, the
    Pallas kernels compiled inside its scan: it compiles and fits the
    chip, and its pair-load contractions (`_route_pair`'s two einsums)
    become float32 multiply-reduce fusions.  No dot of the route stage
    reaches the MXU, where default precision would round the operands
    to bfloat16."""
    from repro.kernels import backend
    from repro.netsim.jx import engine
    from repro.netsim.jx.megabatch import megabatch_programs
    from repro.scenarios import compile_scenario, get_scenario

    monkeypatch.setenv("REPRO_NETSIM_PALLAS", "1")
    monkeypatch.setattr(backend, "pallas_interpret",
                        lambda override=None: False)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    spec = compile_scenario(get_scenario("giga_fabric_storage").with_sim(
        routing="war", backend="jax"))
    (fn, args), = megabatch_programs([spec], n_devices=1)
    compiled = _compile_program(fn, args, one_chip)
    text = compiled.as_text()
    assert "pair_fractions" in text and "tpu_custom_call" in text
    loads = [line for line in text.splitlines()
             if "slot/route/plm,plms->" in line and "reduce(" in line]
    assert len(loads) == 2
    assert all("= f32[" in line for line in loads)
    assert not [line for line in text.splitlines()
                if _MXU_OP.search(line) and "slot/route" in line]
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < DEVICE_BYTES


_SCATTER = re.compile(r"= \w+\[([\d,]*)\]\S* scatter\(")
_SCATTER_SCOPE = re.compile(r'op_name="[^"]*/(slot/\w+)/scatter-add"')


@pytest.mark.parametrize("routing", ["ecmp", "war"])
def test_giga_program_sums_by_contraction_not_scatter(one_chip, monkeypatch,
                                                      routing):
    """The giga point as one megabatch program, compiled for the chip with
    the sums it selects there (`engine.onehot_agg`): the ECMP program
    keeps no scatter-add in its route and host-load stages, and the WAR
    program keeps one, in its route stage, of `P·L·L` buckets: the
    leaf-pair sum.  A compiled scatter names no stage itself; the ops
    that feed its updates do."""
    from repro.netsim.jx import engine, megabatch
    from repro.scenarios import compile_scenario, get_scenario

    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    struct_cfg = megabatch._struct_cfg
    monkeypatch.setattr(megabatch, "_struct_cfg", lambda c: replace(
        struct_cfg(c), ecmp_onehot_bytes=engine.ecmp_onehot_budget("tpu")))
    spec = compile_scenario(get_scenario("giga_fabric_storage").with_sim(
        routing=routing, backend="jax"))
    (fn, args), = megabatch.megabatch_programs([spec], n_devices=1)
    d = _dims("giga_fabric_storage")
    assert engine.onehot_agg(megabatch._struct_cfg(spec), d["F"])
    compiled = _compile_program(fn, args, one_chip)
    text = compiled.as_text()
    shapes = _SCATTER.findall(text)
    scopes = set(_SCATTER_SCOPE.findall(text))
    assert "slot/host_load" not in scopes
    if routing == "ecmp":
        assert not shapes and "slot/route" not in scopes
    else:
        assert shapes == [str(d["P"] * d["L"] * d["L"])]
        assert scopes == {"slot/route"}
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < DEVICE_BYTES
