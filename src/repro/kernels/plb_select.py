"""NIC Plane Load Balancer per-packet selection (Fig. 4) as a Pallas
kernel: two-stage hierarchy —

  1. rate filter: mask planes whose CC allowance < the packet's tx rate
     (or that are ineligible: probe-timed-out);
  2. local queue: among eligible planes pick the shallowest NIC egress
     queue, hash tie-break.

E2E congestion state takes precedence; queue depth breaks ties among
uncongested planes — exactly the paper's hierarchy.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30


def _plb_kernel(rate_ref, elig_ref, queue_ref, tx_ref, hash_ref, out_ref,
                *, n_planes: int, bp: int):
    rate = rate_ref[...].astype(jnp.float32)            # (1, P)
    elig = elig_ref[...] > 0
    queue = queue_ref[...].astype(jnp.float32)
    tx = tx_ref[...].astype(jnp.float32)                # (bp, 1)

    # stage 1 — rate filter (E2E congestion precedence)
    ok = elig & (rate >= tx)                            # (bp, P) broadcast
    any_ok = jnp.any(ok, axis=1, keepdims=True)
    ok = jnp.where(any_ok, ok, elig)                    # fallback: eligible

    # stage 2 — shallowest local egress queue, hashed tie-break
    h = hash_ref[...].astype(jnp.uint32)                # (bp, 1)
    planes = jax.lax.broadcasted_iota(jnp.uint32, (bp, n_planes), 1)
    mix = (h * jnp.uint32(2654435761) + planes * jnp.uint32(97))
    mix = mix ^ (mix >> 16)
    tie = (mix & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    score = jnp.where(ok, queue + 1e-3 * tie, BIG)
    out_ref[...] = jnp.argmin(score, axis=1,
                              keepdims=True).astype(jnp.int32)


def _plane_split_kernel(rate_ref, elig_ref, demand_ref, out_ref,
                        *, mode: str, n_planes: int, min_rate: float):
    """One block of flows: fluid plane split for a static NIC `mode`
    (see `ref.plane_split_ref`).  Pure VPU work on (bp, P) tiles.

    Masks stay 0/1 float32 throughout: Mosaic cannot reduce or
    re-materialise i1 vectors ("Unsupported target bitwidth for
    truncation"), and sums of exact 0/1 floats equal the oracle's
    integer counts bit for bit."""
    rate = rate_ref[...].astype(jnp.float32)             # (bp, P)
    elig = elig_ref[...].astype(jnp.float32)             # 0/1 mask
    demand = demand_ref[...].astype(jnp.float32)         # (bp, 1)
    if mode == "dcqcn":
        out = jnp.minimum(demand * (1.0 / n_planes), rate)
    elif mode == "swlb":
        n_up = jnp.maximum(jnp.sum(elig, axis=1, keepdims=True), 1.0)
        out = jnp.where(elig > 0, demand / n_up, 0.0)
    elif mode == "agg":
        n_up = jnp.maximum(jnp.sum(elig, axis=1, keepdims=True), 1.0)
        shared = jnp.min(rate, axis=1, keepdims=True)
        out = jnp.where(elig > 0, demand * shared / n_up, 0.0)
    else:  # spx: rate filter (E2E precedence) then allowance weighting
        ok = jnp.where(rate > min_rate + 1e-9, elig, 0.0)
        any_ok = jnp.max(ok, axis=1, keepdims=True)
        ok = jnp.where(any_ok > 0, ok, elig) > 0
        w = jnp.where(ok, rate, 0.0)
        s = jnp.sum(w, axis=1, keepdims=True)
        w = jnp.where(s > 0, w / jnp.maximum(s, 1e-12), 1.0 / n_planes)
        out = jnp.minimum(demand * w, jnp.where(ok, rate, 0.0))
    out_ref[...] = out


def plane_split(rate: jax.Array, eligible: jax.Array, demand: jax.Array,
                *, mode: str, min_rate: float = 0.0, bp: int = 256,
                use_pallas: bool = False,
                interpret: Optional[bool] = None) -> jax.Array:
    """Batched fluid plane split — the per-slot NIC hot path of the
    simulator.  `rate`/`eligible`: (F, P); `demand`: (F,).  Returns the
    (F, P) offered matrix.

    With `use_pallas=False` (the default on non-TPU backends, see
    `kernels.backend.pallas_enabled`) this is exactly
    `ref.plane_split_ref` — bit-identical to the engine's historical
    jnp math, which the x64 parity suite pins.  The Pallas path runs
    float32 blocks of `bp` flows on the VPU; `interpret=None` resolves
    via `backend.pallas_interpret` (interpret everywhere but TPU)."""
    from . import backend, ref

    if not use_pallas:
        return ref.plane_split_ref(rate, eligible, demand, mode=mode,
                                   min_rate=min_rate)
    F, P = rate.shape
    bp = min(bp, F)
    pad = (-F) % bp
    if pad:
        rate = jnp.pad(rate, ((0, pad), (0, 0)))
        eligible = jnp.pad(eligible, ((0, pad), (0, 0)))
        demand = jnp.pad(demand, (0, pad))
    n_blk = rate.shape[0] // bp
    kernel = functools.partial(_plane_split_kernel, mode=mode,
                               n_planes=P, min_rate=min_rate)
    out = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((bp, P), lambda i: (i, 0)),
            pl.BlockSpec((bp, P), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, P), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rate.shape[0], P), jnp.float32),
        interpret=backend.pallas_interpret(interpret),
    )(rate.astype(jnp.float32), eligible.astype(jnp.float32),
      demand[:, None].astype(jnp.float32))
    return out[:F].astype(rate.dtype)


def plb_select(rate_allow: jax.Array, eligible: jax.Array,
               local_queue: jax.Array, tx_rate: jax.Array,
               pkt_hash: jax.Array, *, bp: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """rate_allow/eligible/local_queue: (P,); tx_rate/pkt_hash: (N,).
    Returns (N,) int32 plane per packet."""
    from . import backend

    (P,) = rate_allow.shape
    N = pkt_hash.shape[0]
    bp = min(bp, N)
    pad = (-N) % bp
    if pad:
        pkt_hash = jnp.pad(pkt_hash, (0, pad))
        tx_rate = jnp.pad(tx_rate, (0, pad))
    n_blk = pkt_hash.shape[0] // bp

    kernel = functools.partial(_plb_kernel, n_planes=P, bp=bp)
    out = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pkt_hash.shape[0], 1), jnp.int32),
        interpret=backend.pallas_interpret(interpret),
    )(rate_allow[None, :].astype(jnp.float32),
      eligible[None, :].astype(jnp.float32),
      local_queue[None, :].astype(jnp.float32),
      tx_rate[:, None].astype(jnp.float32),
      pkt_hash[:, None].astype(jnp.uint32))
    return out[:N, 0]
