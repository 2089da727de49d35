"""Fused link-load accumulation + bottleneck scaling (the stage-A /
stage-B half of the simulator's per-slot hot path) as Pallas kernels.

Three entry points, mirroring how the engine consumes loads:

  * `bucket_load_bottleneck` — reduce a gathered (P, rows, C) ECMP load
    plan to per-link loads AND their min(1, cap/load) scale factors in
    one pass (dense aggregation mode: the plan rows are leaf×path link
    buckets).
  * `bottleneck` — the elementwise scale factor alone, for loads that
    arrive pre-aggregated (AR/WAR einsums, access links, and the sparse
    aggregation mode).
  * `segment_load` — sparse flow→link accumulation via
    `jax.ops.segment_sum`: memory is bounded by flow count, not
    `leaves² · planes`.  Scatter-adds stay on XLA (TPU scatter lowers
    to efficient sorted-segment ops; a Pallas scatter would serialize
    on the VPU) — kept here so the engine has a single swap point.
    On XLA CPU float64 the scatter expander applies updates in index
    order, i.e. flow order — bit-identical to the NumPy engine's
    sequential `np.add.at` (pinned by tests/test_sparse_agg.py).

With `use_pallas=False` every path is exactly the `ref.py` oracle —
bit-identical to the engine's historical jnp math, which the x64 parity
suite pins.  Pallas paths run float32 row blocks on the VPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-12


def _load_bottleneck_kernel(g_ref, cap_ref, load_ref, frac_ref,
                            *, eps: float):
    g = g_ref[...].astype(jnp.float32)                   # (br, C)
    cap = cap_ref[...].astype(jnp.float32)               # (br, 1)
    load = jnp.sum(g, axis=1, keepdims=True)
    load_ref[...] = load
    frac_ref[...] = jnp.minimum(1.0, cap / jnp.maximum(load, eps))


def bucket_load_bottleneck(g: jax.Array, cap: jax.Array, *,
                           eps: float = EPS,
                           ordered: Optional[bool] = None, br: int = 128,
                           use_pallas: bool = False,
                           interpret: Optional[bool] = None):
    """Fused bucket-sum + bottleneck over a gathered load plan.

    `g`: (P, rows, C) flow rates gathered into link buckets (padded
    entries read a zero row); `cap`: (P, rows) link capacities in the
    same row layout.  Returns `(load, frac)`, both (P, rows).

    `ordered=None` resolves to `g.dtype == float64` — parity mode, where
    the width axis must accumulate strictly left-to-right in flow order
    (see `ref.bucket_sum_ref`).  Ordered sums always take the fallback:
    a sequential loop has no VPU win, and f64 parity never runs Pallas.
    """
    from . import backend, ref

    if ordered is None:
        ordered = g.dtype == jnp.float64
    if not use_pallas or ordered:
        return ref.load_bottleneck_ref(g, cap, eps=eps, ordered=ordered)
    P, R, C = g.shape
    g2 = g.reshape(P * R, C)
    cap2 = cap.reshape(P * R, 1)
    rows = P * R
    br = min(br, rows)
    pad = (-rows) % br
    if pad:
        g2 = jnp.pad(g2, ((0, pad), (0, 0)))
        cap2 = jnp.pad(cap2, ((0, pad), (0, 0)))
    n_blk = g2.shape[0] // br
    kernel = functools.partial(_load_bottleneck_kernel, eps=eps)
    load, frac = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g2.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((g2.shape[0], 1), jnp.float32),
        ],
        interpret=backend.pallas_interpret(interpret),
    )(g2.astype(jnp.float32), cap2.astype(jnp.float32))
    return (load[:rows, 0].reshape(P, R).astype(g.dtype),
            frac[:rows, 0].reshape(P, R).astype(g.dtype))


def _bottleneck_kernel(cap_ref, load_ref, out_ref, *, eps: float):
    cap = cap_ref[...].astype(jnp.float32)
    load = load_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.minimum(1.0, cap / jnp.maximum(load, eps))


def bottleneck(cap: jax.Array, load: jax.Array, *, eps: float = EPS,
               br: int = 256, use_pallas: bool = False,
               interpret: Optional[bool] = None) -> jax.Array:
    """Elementwise min(1, cap/load) scale factor, any matching shape.
    The Pallas path runs the flattened links as `(br, 128)` blocks
    (`backend.to_lanes`)."""
    from . import backend, ref

    if not use_pallas:
        return ref.bottleneck_ref(cap, load, eps=eps)
    slabs, br, n_blk = backend.to_lanes((cap, load), br)
    spec = pl.BlockSpec((br, backend.LANES), lambda i: (i, 0))
    kernel = functools.partial(_bottleneck_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(slabs[0].shape, jnp.float32),
        interpret=backend.pallas_interpret(interpret),
    )(*slabs)
    return backend.from_lanes(out, cap.shape, cap.dtype)


def segment_load(vals: jax.Array, keys: jax.Array,
                 num_segments: int) -> jax.Array:
    """Sparse flow→link accumulation: sum `vals` (any shape) into
    `num_segments` buckets keyed by `keys` (same shape).  Flattening is
    row-major, so per-bucket updates arrive in flow order — the f64
    bit-exactness contract the engine's parity mode relies on."""
    return jax.ops.segment_sum(vals.reshape(-1), keys.reshape(-1),
                               num_segments=num_segments)


def segment_load_chunk(acc: jax.Array, vals: jax.Array,
                       keys: jax.Array) -> jax.Array:
    """One streaming step of `segment_load`: add this chunk's `vals`
    into the flat accumulator `acc` (shape `(num_segments,)`), keyed by
    `keys`.  Both this scatter-add and `segment_sum` apply duplicate
    updates in index (= flow) order on the XLA CPU f64 expander, so
    folding chunks left-to-right reproduces the monolithic call's
    per-bucket addition chain bit for bit — the invariant the chunked
    engine's x64 parity tests pin.  Pad flows must carry exact +0.0
    values (the engine's inert-pad contract), which cannot perturb any
    partial sum of non-negative rates."""
    return acc.at[keys.reshape(-1)].add(vals.reshape(-1))
