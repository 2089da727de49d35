"""Jit'd public wrappers for the Pallas kernels.

On non-TPU backends the kernels execute via ``interpret=True`` (Python
interpreter of the kernel body — used for CPU validation); on TPU they
compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import decode_attention as _da
from . import jsq_route as _jr
from . import link_load as _ll
from . import plb_select as _ps
from . import int8_codec as _ic
from . import queue_ecn as _qe


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    """(B,H,S,D) fused attention; GQA callers repeat KV heads first."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=_interpret())


def flash_attention_bshd(q, k, v, *, causal: bool = True,
                         window: int = 0, bq: int = 128, bk: int = 128):
    """Model-layout wrapper: q (B,S,Hq,D), k/v (B,S,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hkv != Hq:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    out = flash_attention(q.transpose(0, 2, 1, 3),
                          k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3),
                          causal=causal, window=window, bq=bq, bk=bk)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, lengths, *, bk: int = 512):
    return _da.decode_attention(q, k, v, lengths, bk=bk,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("nbins", "qmax", "bp"))
def jsq_route(queues, up_mask, weights, pkt_hash, *, nbins: int = 16,
              qmax: float = 1.0, bp: int = 256):
    return _jr.jsq_route(queues, up_mask, weights, pkt_hash, nbins=nbins,
                         qmax=qmax, bp=bp, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bp",))
def plb_select(rate_allow, eligible, local_queue, tx_rate, pkt_hash,
               *, bp: int = 256):
    return _ps.plb_select(rate_allow, eligible, local_queue, tx_rate,
                          pkt_hash, bp=bp, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("mode", "min_rate", "bp"))
def plane_split(rate, eligible, demand, *, mode: str,
                min_rate: float = 0.0, bp: int = 256):
    """Batched (F, P) fluid plane split (Pallas path; the simulator
    itself dispatches via `plb_select.plane_split` so non-TPU backends
    keep the bit-exact jnp fallback)."""
    return _ps.plane_split(rate, eligible, demand, mode=mode,
                           min_rate=min_rate, bp=bp, use_pallas=True,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("nbins", "temperature",
                                             "qmax", "br"))
def pair_fractions(q, cap, w, *, nbins: int = 16,
                   temperature: float = 1.0, qmax: float = 8.0,
                   br: int = 128):
    """(…, S) quantized-JSQ spine fractions (Pallas path; see
    `jsq_route.pair_fractions` for the dispatching entry point)."""
    return _jr.pair_fractions(q, cap, w, nbins=nbins,
                              temperature=temperature, qmax=qmax, br=br,
                              use_pallas=True, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "br"))
def bucket_load_bottleneck(g, cap, *, eps: float = _ll.EPS,
                           br: int = 128):
    """Fused (P, rows, C) load-plan sum + bottleneck scaling (Pallas
    path; the simulator dispatches via
    `link_load.bucket_load_bottleneck`, keeping the bit-exact jnp
    fallback off-TPU and the ordered f64 parity sum everywhere)."""
    return _ll.bucket_load_bottleneck(g, cap, eps=eps, ordered=False,
                                      br=br, use_pallas=True,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "br"))
def bottleneck(cap, load, *, eps: float = _ll.EPS, br: int = 256):
    """Elementwise min(1, cap/load) link scale factor (Pallas path)."""
    return _ll.bottleneck(cap, load, eps=eps, br=br, use_pallas=True,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("q_cap", "eps", "br"))
def queue_update(q, load, cap, *, q_cap: float, eps: float = _qe.EPS,
                 br: int = 256):
    """Fluid queue integrator + utilization (Pallas path; see
    `queue_ecn.queue_update` for the dispatching entry point)."""
    return _qe.queue_update(q, load, cap, q_cap=q_cap, eps=eps, br=br,
                            use_pallas=True, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=(
    "mode", "base_rtt_us", "slot_us", "ecn_thresh", "target_rtt_us",
    "min_rate", "md", "ai", "rtt_gain", "dcqcn_ai", "alpha_g", "bp"))
def nic_update(qmean, rate, alpha, esr, *, mode: str, base_rtt_us: float,
               slot_us: float, ecn_thresh: float, target_rtt_us: float,
               min_rate: float, md: float, ai: float, rtt_gain: float,
               dcqcn_ai: float, alpha_g: float, bp: int = 256):
    """Fused RTT/ECN + CC rate step (Pallas path; see
    `queue_ecn.nic_update` for the dispatching entry point)."""
    return _qe.nic_update(qmean, rate, alpha, esr, mode=mode,
                          base_rtt_us=base_rtt_us, slot_us=slot_us,
                          ecn_thresh=ecn_thresh,
                          target_rtt_us=target_rtt_us,
                          min_rate=min_rate, md=md, ai=ai,
                          rtt_gain=rtt_gain, dcqcn_ai=dcqcn_ai,
                          alpha_g=alpha_g, bp=bp, use_pallas=True,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("br",))
def int8_encode(x, noise, *, br: int = 256):
    return _ic.int8_encode(x, noise, br=br, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("br", "dtype"))
def int8_decode(q, scale, *, br: int = 256, dtype=jnp.float32):
    return _ic.int8_decode(q, scale, br=br, dtype=dtype,
                           interpret=_interpret())
