"""Switch per-packet adaptive routing (quantized JSQ + weighted-AR, §4.1,
§4.4.2) as a Pallas kernel — the simulator's hot loop and the kernel-level
expression of the paper's in-network mechanism.

For each packet: score every egress port by quantized queue depth divided
by its remote-capacity weight; pick the min-score port with a hash-based
tie-break; failed ports score +inf.  Pure VPU work: (bp, ports) vector
ops per block of packets.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30


def _jsq_kernel(q_ref, up_ref, w_ref, hash_ref, port_ref,
                *, nbins: int, qmax: float, n_ports: int, bp: int):
    queues = q_ref[...].astype(jnp.float32)            # (1, ports)
    up = up_ref[...] > 0                               # (1, ports)
    w = w_ref[...].astype(jnp.float32)
    qbin = jnp.floor(jnp.clip(queues / qmax, 0.0, 1.0 - 1e-6) * nbins)
    score = (qbin + 1.0) / jnp.maximum(w, 1e-6)
    score = jnp.where(up, score, BIG)                  # (1, ports)

    h = hash_ref[...].astype(jnp.uint32)               # (bp, 1)
    ports = jax.lax.broadcasted_iota(jnp.uint32, (bp, n_ports), 1)
    # per-packet hashed tie-break in [0, 1): decorrelates equal-score picks
    mix = (h * jnp.uint32(2654435761) + ports * jnp.uint32(40503))
    mix = mix ^ (mix >> 16)
    tie = (mix & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    total = score + tie * 0.5                          # (bp, ports)
    port_ref[...] = jnp.argmin(total, axis=1,
                               keepdims=True).astype(jnp.int32)


def _pair_score_kernel(q_ref, cap_ref, w_ref, out_ref, *, nbins: int,
                       temperature: float, qmax: float):
    """One block of (src-leaf, dst-leaf) rows: quantized-JSQ scoring +
    softmax over the spine axis (`ref.pair_score_softmax_ref`)."""
    q = q_ref[...].astype(jnp.float32)                   # (br, S)
    cap = cap_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    up = cap > 1e-9
    qbin = jnp.floor(jnp.clip(q / qmax, 0.0, 1.0 - 1e-9) * nbins) + 1.0
    score = qbin / jnp.maximum(w, 1e-9)
    logit = jnp.where(up, -score / temperature, -BIG)
    logit -= jnp.max(logit, axis=-1, keepdims=True)
    e = jnp.exp(logit)
    sums = jnp.sum(e, axis=-1, keepdims=True)
    out_ref[...] = jnp.where(sums > 0, e / jnp.maximum(sums, 1e-30), 0.0)


def pair_fractions(q: jax.Array, cap: jax.Array, w: jax.Array, *,
                   nbins: int = 16, temperature: float = 1.0,
                   qmax: float = 8.0, br: int = 128,
                   use_pallas: bool = False,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Spine-selection fractions for every (plane, src-leaf, dst-leaf)
    path — the per-slot AR/WAR hot path of the simulator.  `q`/`cap`/`w`
    are (..., S): summed up+down queue depth, min(up, down) path
    capacity, and the capacity-(×remote)-weight; returns (..., S)
    fractions summing to 1 over alive spines.

    With `use_pallas=False` this is exactly `ref.pair_score_softmax_ref`
    (bit-identical to the engine's historical jnp math).  The Pallas
    path flattens the leading axes into rows of `br` and scores each on
    the VPU in float32; `interpret=None` resolves via
    `backend.pallas_interpret` (interpret everywhere but TPU)."""
    from . import backend, ref

    if not use_pallas:
        return ref.pair_score_softmax_ref(q, cap, w, nbins=nbins,
                                          temperature=temperature,
                                          qmax=qmax)
    lead = q.shape[:-1]
    S = q.shape[-1]
    R = 1
    for d in lead:
        R *= d
    q2, cap2, w2 = (a.reshape(R, S) for a in (q, cap, w))
    br = min(br, R)
    pad = (-R) % br
    if pad:
        q2 = jnp.pad(q2, ((0, pad), (0, 0)))
        cap2 = jnp.pad(cap2, ((0, pad), (0, 0)))
        w2 = jnp.pad(w2, ((0, pad), (0, 0)))
    n_blk = q2.shape[0] // br
    kernel = functools.partial(_pair_score_kernel, nbins=nbins,
                               temperature=temperature, qmax=qmax)
    out = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((br, S), lambda i: (i, 0)),
            pl.BlockSpec((br, S), lambda i: (i, 0)),
            pl.BlockSpec((br, S), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, S), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q2.shape[0], S), jnp.float32),
        interpret=backend.pallas_interpret(interpret),
        name="pair_fractions",
    )(q2.astype(jnp.float32), cap2.astype(jnp.float32),
      w2.astype(jnp.float32))
    return out[:R].reshape(*lead, S).astype(q.dtype)


def jsq_route(queues: jax.Array, up_mask: jax.Array, weights: jax.Array,
              pkt_hash: jax.Array, *, nbins: int = 16, qmax: float = 1.0,
              bp: int = 256,
              interpret: Optional[bool] = None) -> jax.Array:
    """queues/up_mask/weights: (ports,); pkt_hash: (N,) uint32.
    Returns (N,) int32 egress port per packet."""
    from . import backend

    (n_ports,) = queues.shape
    N = pkt_hash.shape[0]
    bp = min(bp, N)
    pad = (-N) % bp
    if pad:
        pkt_hash = jnp.pad(pkt_hash, (0, pad))
    n_blk = pkt_hash.shape[0] // bp

    kernel = functools.partial(_jsq_kernel, nbins=nbins, qmax=qmax,
                               n_ports=n_ports, bp=bp)
    out = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((1, n_ports), lambda i: (0, 0)),
            pl.BlockSpec((1, n_ports), lambda i: (0, 0)),
            pl.BlockSpec((1, n_ports), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pkt_hash.shape[0], 1), jnp.int32),
        interpret=backend.pallas_interpret(interpret),
        name="jsq_route",
    )(queues[None, :].astype(jnp.float32),
      up_mask[None, :].astype(jnp.float32),
      weights[None, :].astype(jnp.float32),
      pkt_hash[:, None].astype(jnp.uint32))
    return out[:N, 0]
