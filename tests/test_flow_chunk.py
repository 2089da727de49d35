"""Chunked flow streaming (`JxConfig.flow_chunk`) vs the monolithic
sparse path.

The streaming engine (`netsim/jx/chunked.py`) runs the flow axis
through `_slot_step`'s sparse path in fixed-size chunks, folding each
chunk's scatter-add into flat per-link accumulators.  On CPU f64 both
that fold and the monolithic `segment_sum` apply per-bucket updates in
flow order, and the per-flow NIC/completion tail runs monolithically
outside the chunk scan — so chunked results are *bit-identical* to the
monolithic engine at x64, for every chunk length including ones that
don't divide the flow count.  These tests pin that contract on both
topology kinds, its composition with `REPRO_JX_COMPACT`, the megabatch
dispatch path, and the chunk-size-independence of delivered bytes.
"""
import os
import warnings

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # deterministic coverage below still runs
    HAVE_HYPOTHESIS = False

from repro.scenarios.registry import get_scenario
from repro.scenarios.runner import run_point

from test_sparse_agg import _assert_close

SETTINGS = dict(max_examples=6, deadline=None)

# real flow populations (64 each), one per topology kind — chunk sizes
# below exercise singleton chunks, a non-divisible tail (17), and a
# chunk longer than the flow axis
SCN = {"leaf_spine": "fig8_bisection",
       "fat_tree": "ft_core_failure_resiliency"}
CHUNKS = (1, 17, 1024, 64)


def _run_chunk(spec, chunk, extra_env=()):
    """`run_point` with `REPRO_JX_FLOW_CHUNK` (and any extra env pairs)
    pinned for the call; 0/None restores the monolithic path."""
    pairs = (("REPRO_JX_FLOW_CHUNK",
              str(chunk) if chunk else None),) + tuple(extra_env)
    prev = {k: os.environ.get(k) for k, _ in pairs}
    for k, v in pairs:
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        return run_point(spec).to_dict()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.parametrize("kind", ["leaf_spine", "fat_tree"])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_bit_identical_x64(kind, chunk):
    """The tentpole contract: every chunk length — singleton, a
    non-divisible 17, longer-than-F, and exactly F — reproduces the
    monolithic sparse engine bit for bit at x64."""
    with jax.enable_x64(True):
        spec = get_scenario(SCN[kind]).with_sim(slots=48, backend="jax")
        mono = _run_chunk(spec, 0)
        chunked = _run_chunk(spec, chunk)
    _assert_close(mono, chunked, rtol=0.0)


@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
def test_chunked_bit_identical_x64_routings(routing):
    """Every routing branch has its own chunked transcription (pair
    tables vs per-stage ECMP fractions) — pin each at the awkward
    non-divisible chunk length."""
    with jax.enable_x64(True):
        spec = get_scenario("fig8_bisection").with_sim(
            slots=48, routing=routing, nic="dcqcn", backend="jax")
        mono = _run_chunk(spec, 0)
        chunked = _run_chunk(spec, 17)
    _assert_close(mono, chunked, rtol=0.0)


if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(kind=st.sampled_from(["leaf_spine", "fat_tree"]),
           chunk=st.integers(1, 96))
    def test_delivered_bytes_chunk_size_invariant(kind, chunk):
        """The named invariant: total delivered bytes (flow count x the
        mean per-flow goodput integral the engine reports) do not depend
        on how the flow axis was chunked."""
        with jax.enable_x64(True):
            spec = get_scenario(SCN[kind]).with_sim(slots=36,
                                                    backend="jax")
            mono = _run_chunk(spec, 0)
            chunked = _run_chunk(spec, chunk)
        assert chunked["mean_goodput"] == mono["mean_goodput"]
        _assert_close(mono, chunked, rtol=0.0)


def test_compact_carry_composes_with_flow_chunk_f32():
    """S1: `REPRO_JX_COMPACT` (int8 probe counters) and `flow_chunk`
    compose — the chunked scan carries the compact NIC state through
    the chunk axis, and f32 results stay bit-identical to the
    wide-carry chunked run."""
    spec = get_scenario("fig8_bisection").with_sim(
        slots=40, routing="ar", nic="esr", backend="jax")
    wide = _run_chunk(spec, 17)
    compact = _run_chunk(spec, 17, extra_env=(("REPRO_JX_COMPACT", "1"),))
    _assert_close(wide, compact, rtol=0.0)


def test_chunked_megabatch_row_identity_x64():
    """The megabatch dispatcher wires `flow_chunk` through its
    structural cfg and rounds the flow bucket to a chunk multiple; a
    forced awkward chunk must leave every row of a mixed grid identical
    to the monolithic megabatch run."""
    from repro.experiments import Axis, Experiment, execute_points, product

    exp = Experiment(
        name="test_flow_chunk.mb", base="flap_during_incast",
        axes=product(Axis("sim.routing", ("ar", "war", "ecmp")),
                     Axis("sim.nic", ("spx", "swlb")),
                     Axis("seed", (0, 1)),
                     Axis("sim.slots", (80,))))
    points = [p.spec for p in exp.points()]
    with jax.enable_x64(True):
        mono = execute_points(points, backend="jax")
        prev = os.environ.get("REPRO_JX_FLOW_CHUNK")
        os.environ["REPRO_JX_FLOW_CHUNK"] = "17"
        try:
            chunked = execute_points(points, backend="jax")
        finally:
            if prev is None:
                del os.environ["REPRO_JX_FLOW_CHUNK"]
            else:
                os.environ["REPRO_JX_FLOW_CHUNK"] = prev
    for p, a, b in zip(points, mono, chunked):
        assert a.to_row() == b.to_row(), p.name
        assert b.mean_goodput == pytest.approx(a.mean_goodput, abs=1e-5)


def test_chunked_no_donation_warnings_leak():
    """S1: the chunked megabatch launch donates its host-built carry;
    the expected 'donated buffers were not usable' compile chatter must
    be swallowed by the dispatcher, not surface to sweep callers."""
    from repro.experiments import execute_points

    spec = get_scenario("fig8_bisection").with_sim(slots=30,
                                                   backend="jax")
    prev = os.environ.get("REPRO_JX_FLOW_CHUNK")
    os.environ["REPRO_JX_FLOW_CHUNK"] = "16"
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            execute_points([spec], backend="jax")
    finally:
        if prev is None:
            del os.environ["REPRO_JX_FLOW_CHUNK"]
        else:
            os.environ["REPRO_JX_FLOW_CHUNK"] = prev
    leaked = [w for w in caught
              if "donated" in str(w.message).lower()]
    assert not leaked, [str(w.message) for w in leaked]
