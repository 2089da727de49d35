"""Fused queue/ECN/NIC-update (the control half of the simulator's
per-slot hot path) as Pallas kernels.

Two entry points:

  * `queue_update` — the fluid queue integrator + utilization for one
    link stage: `q' = clip(q + (load-cap)/cap, 0, q_cap)`, dead links
    pinned empty.  Elementwise over any (matching) shape.
  * `nic_update` — queue-derived RTT/ECN signals fused with one step of
    the CC rate law (`spx` per-plane AIMD — also swlb's law — `dcqcn`,
    or the aggregate `agg` context used by 'global'/'esr' NICs).  The
    probe/eligibility bookkeeping stays in the engine: it is bool/int
    select logic with no arithmetic to fuse.

With `use_pallas=False` both are exactly the `ref.py` oracles —
bit-identical to the engine's historical jnp math, which the x64 parity
suite pins.  Pallas paths run float32 blocks of `bp` flows on the VPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-12


def _queue_update_kernel(q_ref, load_ref, cap_ref, qn_ref, util_ref,
                         *, q_cap: float, eps: float):
    q = q_ref[...].astype(jnp.float32)
    load = load_ref[...].astype(jnp.float32)
    cap = cap_ref[...].astype(jnp.float32)
    denom = jnp.maximum(cap, eps)
    qn = jnp.clip(q + (load - cap) / denom, 0.0, q_cap)
    qn_ref[...] = jnp.where(cap <= eps, 0.0, qn)
    util_ref[...] = load / denom


def queue_update(q: jax.Array, load: jax.Array, cap: jax.Array, *,
                 q_cap: float, eps: float = EPS, br: int = 256,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None):
    """One slot of fluid queue evolution.  Returns `(q_new, util)`.
    The Pallas path runs the flattened links as `(br, 128)` blocks
    (`backend.to_lanes`); zero-capacity pad links stay finite."""
    from . import backend, ref

    if not use_pallas:
        return ref.queue_update_ref(q, load, cap, q_cap=q_cap, eps=eps)
    slabs, br, n_blk = backend.to_lanes((q, load, cap), br)
    spec = pl.BlockSpec((br, backend.LANES), lambda i: (i, 0))
    kernel = functools.partial(_queue_update_kernel, q_cap=q_cap,
                               eps=eps)
    qn, util = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[spec] * 3,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct(slabs[0].shape, jnp.float32)] * 2,
        interpret=backend.pallas_interpret(interpret),
    )(*slabs)
    return (backend.from_lanes(qn, q.shape, q.dtype),
            backend.from_lanes(util, q.shape, q.dtype))


def _nic_update_kernel(qmean_ref, rate_ref, alpha_ref, esr_ref,
                       rtt_ref, ecn_ref, rate_out_ref, alpha_out_ref,
                       *, mode: str, base_rtt_us: float, slot_us: float,
                       ecn_thresh: float, target_rtt_us: float,
                       min_rate: float, md: float, ai: float,
                       rtt_gain: float, dcqcn_ai: float, alpha_g: float):
    qmean = qmean_ref[...].astype(jnp.float32)           # (bp, P)
    rate = rate_ref[...].astype(jnp.float32)
    alpha = alpha_ref[...].astype(jnp.float32)
    esr = esr_ref[...] > 0                               # (bp, 1)
    rtt = base_rtt_us + qmean * slot_us * 0.5
    ecn = jnp.where(qmean > ecn_thresh,
                    jnp.minimum(1.0, qmean / (4 * ecn_thresh)), 0.0)
    rtt_ref[...] = rtt
    ecn_ref[...] = ecn
    if mode == "dcqcn":
        ecn_any = jnp.max(ecn, axis=1, keepdims=True)
        alpha_new = (1 - alpha_g) * alpha + alpha_g * (ecn_any > 0)
        cut = rate * (1 - alpha_new / 2)
        grow = jnp.minimum(rate + dcqcn_ai, 1.0)
        new = jnp.clip(jnp.where(ecn_any > 0, cut, grow), min_rate, 1.0)
        rate_out_ref[...] = new
        alpha_out_ref[...] = alpha_new
        return
    if mode == "agg":
        agg_ecn = jnp.max(ecn, axis=1, keepdims=True)
        agg_rtt = jnp.max(rtt, axis=1, keepdims=True)
        cut = rate * md
        rtt_err = (agg_rtt - target_rtt_us) / target_rtt_us
        trim = rate * (1 - rtt_gain * jnp.clip(rtt_err, 0, 2))
        grow = jnp.minimum(rate + ai, 1.0)
        new = jnp.where(agg_ecn > 0, cut,
                        jnp.where(rtt_err > 0.25, trim, grow))
        new = new * jnp.where(jnp.logical_and(esr, agg_ecn > 0),
                              0.85, 1.0)
        rate_out_ref[...] = jnp.clip(new, min_rate, 1.0)
        alpha_out_ref[...] = alpha
        return
    rtt_err = (rtt - target_rtt_us) / target_rtt_us
    cut = rate * (md + (1 - md) * jnp.clip(1 - ecn, 0, 1))
    trim = rate * (1 - rtt_gain * jnp.clip(rtt_err, 0, 2))
    grow = jnp.minimum(rate + ai, 1.0)
    rate_out_ref[...] = jnp.clip(
        jnp.where(ecn > 0, cut, jnp.where(rtt_err > 0.25, trim, grow)),
        min_rate, 1.0)
    alpha_out_ref[...] = alpha


def nic_update(qmean: jax.Array, rate: jax.Array, alpha: jax.Array,
               esr: jax.Array, *, mode: str, base_rtt_us: float,
               slot_us: float, ecn_thresh: float, target_rtt_us: float,
               min_rate: float, md: float, ai: float, rtt_gain: float,
               dcqcn_ai: float, alpha_g: float, bp: int = 256,
               use_pallas: bool = False,
               interpret: Optional[bool] = None):
    """Fused RTT/ECN + CC rate step.  `qmean`/`rate`/`alpha`: (F, P);
    `esr`: (F, 1) bool.  Returns `(rtt, ecn, rate_new, alpha_new)`."""
    from . import backend, ref

    if mode not in ("spx", "dcqcn", "agg"):
        raise ValueError(f"unknown nic-update mode {mode!r}")
    if not use_pallas:
        return ref.nic_update_ref(
            qmean, rate, alpha, esr, mode=mode, base_rtt_us=base_rtt_us,
            slot_us=slot_us, ecn_thresh=ecn_thresh,
            target_rtt_us=target_rtt_us, min_rate=min_rate, md=md, ai=ai,
            rtt_gain=rtt_gain, dcqcn_ai=dcqcn_ai, alpha_g=alpha_g)
    F, P = qmean.shape
    bp = min(bp, F)
    pad = (-F) % bp
    q2, r2, a2 = qmean, rate, alpha
    e2 = esr
    if pad:
        q2 = jnp.pad(q2, ((0, pad), (0, 0)))
        r2 = jnp.pad(r2, ((0, pad), (0, 0)))
        a2 = jnp.pad(a2, ((0, pad), (0, 0)))
        e2 = jnp.pad(e2, ((0, pad), (0, 0)))
    n_blk = q2.shape[0] // bp
    kernel = functools.partial(
        _nic_update_kernel, mode=mode, base_rtt_us=base_rtt_us,
        slot_us=slot_us, ecn_thresh=ecn_thresh,
        target_rtt_us=target_rtt_us, min_rate=min_rate, md=md, ai=ai,
        rtt_gain=rtt_gain, dcqcn_ai=dcqcn_ai, alpha_g=alpha_g)
    rtt, ecn, rate_new, alpha_new = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((bp, P), lambda i: (i, 0)),
            pl.BlockSpec((bp, P), lambda i: (i, 0)),
            pl.BlockSpec((bp, P), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bp, P), lambda i: (i, 0))] * 4,
        out_shape=[jax.ShapeDtypeStruct((q2.shape[0], P),
                                        jnp.float32)] * 4,
        interpret=backend.pallas_interpret(interpret),
    )(q2.astype(jnp.float32), r2.astype(jnp.float32),
      a2.astype(jnp.float32), e2.astype(jnp.float32))
    return (rtt[:F].astype(qmean.dtype), ecn[:F].astype(qmean.dtype),
            rate_new[:F].astype(rate.dtype),
            alpha_new[:F].astype(alpha.dtype))
