"""Sparse (segment-summed) load aggregation vs the dense gather-plan
path, and the compact-carry / fp32-drift contracts.

The sparse path keys `segment_sum` by (plane, link) exactly in flow
order, which on CPU f64 matches the sequential `np.add.at` of the numpy
engine bit-for-bit — so dense-vs-sparse must agree to the same 1e-5 the
numpy↔jax parity suite pins, across both topology kinds and every
routing mode.  Hypothesis drives the shapes/seeds.
"""
import os

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

SETTINGS = dict(max_examples=8, deadline=None)

SCN = {"leaf_spine": "fig12_plane_flap",
       "fat_tree": "ft_core_failure_resiliency"}


def _run_agg(spec, mode):
    prev = os.environ.get("REPRO_JX_AGG")
    os.environ["REPRO_JX_AGG"] = mode
    try:
        return run_point(spec).to_dict()
    finally:
        if prev is None:
            del os.environ["REPRO_JX_AGG"]
        else:
            os.environ["REPRO_JX_AGG"] = prev


def _assert_close(a, b, rtol, path=""):
    assert type(a) is type(b) or (
        isinstance(a, (int, float)) and isinstance(b, (int, float))), path
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            _assert_close(a[k], b[k], rtol, f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_close(x, y, rtol, f"{path}[{i}]")
    elif isinstance(a, float):
        assert np.isclose(a, b, rtol=rtol, atol=1e-7, equal_nan=True), \
            f"{path}: {a} vs {b}"
    else:
        assert a == b, f"{path}: {a} vs {b}"


def _check_sparse_matches_dense(kind, routing, nic, seed):
    with jax.enable_x64(True):
        spec = get_scenario(SCN[kind]).with_sim(
            slots=40, routing=routing, nic=nic, seed=seed,
            backend="jax")
        dense = _run_agg(spec, "dense")
        sparse = _run_agg(spec, "sparse")
    _assert_close(dense, sparse, rtol=1e-5)


@pytest.mark.parametrize("kind", ["leaf_spine", "fat_tree"])
@pytest.mark.parametrize("routing", ["ar", "war", "ecmp"])
def test_sparse_matches_dense_x64(kind, routing):
    """Deterministic cross: both topology kinds x every routing mode."""
    _check_sparse_matches_dense(kind, routing, "dcqcn", 0)


if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(kind=st.sampled_from(["leaf_spine", "fat_tree"]),
           routing=st.sampled_from(["ar", "war", "ecmp"]),
           nic=st.sampled_from(["spx", "dcqcn", "esr"]),
           seed=st.integers(0, 3))
    def test_sparse_matches_dense_x64_property(kind, routing, nic, seed):
        _check_sparse_matches_dense(kind, routing, nic, seed)


def test_sparse_matches_numpy_engine_x64():
    """Under x64 the sparse segment-sum is flow-ordered like the numpy
    engine's `np.add.at`, so it must hit the full cross-backend parity
    tolerance too — not just agree with the dense jax path."""
    with jax.enable_x64(True):
        spec = get_scenario("fig12_plane_flap").with_sim(
            slots=40, routing="war", nic="dcqcn", backend="jax")
        sparse = _run_agg(spec, "sparse")
        ref = run_point(spec.with_sim(backend="numpy")).to_dict()
    _assert_close(ref, sparse, rtol=1e-5)


def test_compact_carry_bit_identical_f32():
    """REPRO_JX_COMPACT only narrows the probe counter to int8; the
    saturating bump (`min(miss+1, probe_timeout)`) is applied in both
    paths, so f32 results are bit-identical, not merely close."""
    spec = get_scenario("fig12_plane_flap").with_sim(
        slots=40, routing="ar", nic="esr", backend="jax")
    base = run_point(spec).to_dict()
    prev = os.environ.get("REPRO_JX_COMPACT")
    os.environ["REPRO_JX_COMPACT"] = "1"
    try:
        compact = run_point(spec).to_dict()
    finally:
        if prev is None:
            del os.environ["REPRO_JX_COMPACT"]
        else:
            os.environ["REPRO_JX_COMPACT"] = prev
    _assert_close(base, compact, rtol=0.0)


def test_f32_carry_drift_vs_f64_bounded():
    """Parity mode off (f32 carry) is the large-scale production
    configuration; pin how far its headline metrics may drift from the
    f64 reference so a silently-catastrophic precision regression (e.g.
    accumulating goodput in f16, or the old un-clamped probe counter
    overflowing) fails loudly."""
    spec = get_scenario("fig12_plane_flap").with_sim(
        slots=60, routing="war", nic="dcqcn", backend="jax")
    f32 = run_point(spec)
    with jax.enable_x64(True):
        f64 = run_point(spec)
    assert f32.mean_goodput == pytest.approx(f64.mean_goodput, rel=1e-3)
    assert f32.isolation_index == pytest.approx(f64.isolation_index,
                                                rel=1e-3, abs=1e-6)
    # open-loop scenario: no finite transfers, so the tail is NaN in
    # both precisions — anything else is a drift bug
    assert np.isnan(f32.completion_tail) == np.isnan(f64.completion_tail)
    if not np.isnan(f64.completion_tail):
        assert f32.completion_tail == pytest.approx(
            f64.completion_tail, rel=1e-3, abs=1e-6)


def test_giga_point_plans_one_sparse_program():
    """The 4,096-host, 102,400-flow registry point stays on the sparse
    aggregation path and fuses into one program — one launch and one
    compile.  Planned with `megabatch_programs`, never run."""
    from repro.netsim.jx import megabatch
    from repro.scenarios import compile_scenario

    compiled = compile_scenario(
        get_scenario("giga_fabric_storage").with_sim(backend="jax"))
    assert compiled.spec.topo.n_hosts >= 4096
    assert len(compiled.flows) >= 100_000
    (fn, args), = megabatch.megabatch_programs([compiled], n_devices=1)
    cfg = megabatch._struct_cfg(compiled)
    assert cfg.agg_mode == "sparse" and not cfg.flow_chunk
    # one lane: the 102,400 flows in their 131,072-flow bucket
    assert args[2].src.shape == (1, 131_072)
