"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int = 0) -> jax.Array:
    """q: (B,H,Sq,D); k/v: (B,H,Sk,D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths) -> jax.Array:
    """q: (B,H,1,D); k/v: (B,H,S,D); lengths: (B,)."""
    B, H, _, D = q.shape
    S = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    valid = jnp.arange(S)[None, None, None, :] < \
        lengths[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def jsq_route_ref(queues, up_mask, weights, pkt_hash, *, nbins: int = 16,
                  qmax: float = 1.0) -> jax.Array:
    qbin = jnp.floor(jnp.clip(queues / qmax, 0.0, 1.0 - 1e-6) * nbins)
    score = (qbin + 1.0) / jnp.maximum(weights, 1e-6)
    score = jnp.where(up_mask > 0, score, 1e30)
    n_ports = queues.shape[0]
    ports = jnp.arange(n_ports, dtype=jnp.uint32)[None, :]
    h = pkt_hash.astype(jnp.uint32)[:, None]
    mix = (h * jnp.uint32(2654435761) + ports * jnp.uint32(40503))
    mix = mix ^ (mix >> 16)
    tie = (mix & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    return jnp.argmin(score[None, :] + tie * 0.5, axis=1).astype(jnp.int32)


def plb_select_ref(rate_allow, eligible, local_queue, tx_rate,
                   pkt_hash) -> jax.Array:
    P = rate_allow.shape[0]
    elig = eligible > 0
    ok = elig[None, :] & (rate_allow[None, :] >= tx_rate[:, None])
    any_ok = jnp.any(ok, axis=1, keepdims=True)
    ok = jnp.where(any_ok, ok, elig[None, :])
    planes = jnp.arange(P, dtype=jnp.uint32)[None, :]
    h = pkt_hash.astype(jnp.uint32)[:, None]
    mix = (h * jnp.uint32(2654435761) + planes * jnp.uint32(97))
    mix = mix ^ (mix >> 16)
    tie = (mix & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    score = jnp.where(ok, local_queue[None, :] + 1e-3 * tie, 1e30)
    return jnp.argmin(score, axis=1).astype(jnp.int32)


def plane_split_ref(rate, eligible, demand, *, mode: str,
                    min_rate: float = 0.0) -> jax.Array:
    """Fluid NIC plane split — the batched (F, P) twin of `plb_select`
    that the simulator's slot step runs every slot (and the jnp fallback
    on non-TPU backends).  `rate`/`eligible`: (F, P) per-plane CC
    allowance and PLB eligibility; `demand`: (F,) offered rate.

    mode:
      'spx'   — rate-filter planes (allowance > min_rate), then weight
                by allowance: the paper's two-stage PLB hierarchy in
                fluid form.
      'dcqcn' — plane-oblivious equal split, capped by allowance.
      'agg'   — one aggregate context ('global'/'esr' NICs): min
                allowance shared equally across eligible planes.
      'swlb'  — software LB: equal split over eligible planes only.
    """
    P = rate.shape[-1]
    if mode == "dcqcn":
        w = jnp.ones_like(rate) / P
        return jnp.minimum(demand[:, None] * w, rate)
    if mode == "swlb":
        elig = eligible
        n_up = jnp.maximum(elig.sum(1, keepdims=True), 1)
        return jnp.where(elig, demand[:, None] / n_up, 0.0)
    if mode == "agg":
        elig = eligible
        n_up = jnp.maximum(elig.sum(1, keepdims=True), 1)
        shared = rate.min(1, keepdims=True)
        return jnp.where(elig, demand[:, None] * shared / n_up, 0.0)
    if mode != "spx":
        raise ValueError(f"unknown plane-split mode {mode!r}")
    elig = eligible & (rate > min_rate + 1e-9)
    any_ok = elig.any(1, keepdims=True)
    elig = jnp.where(any_ok, elig, eligible)
    w = jnp.where(elig, rate, 0.0)
    s = w.sum(1, keepdims=True)
    w = jnp.where(s > 0, w / jnp.maximum(s, 1e-12), 1.0 / P)
    return jnp.minimum(demand[:, None] * w, jnp.where(elig, rate, 0.0))


def jsq_bin(q, *, nbins: int, qmax: float) -> jax.Array:
    """Quantized queue depth: bins 1..nbins over [0, qmax], a queue at
    or past `qmax` in the top one.  The top bin is a cap: `q / qmax`
    clipped to 1 - 1e-9 instead would be 1.0 in float32 and score a
    saturated path nbins + 1.  In float64 both forms give the same bin
    (`FluidFabric._jsq_softmax`)."""
    return jnp.minimum(jnp.floor(jnp.clip(q / qmax, 0.0, 1.0) * nbins),
                       nbins - 1) + 1.0


def pair_score_softmax_ref(q, cap, w, *, nbins: int, temperature: float,
                           qmax: float = 8.0) -> jax.Array:
    """Quantized-JSQ spine scoring + softmax over the trailing spine
    axis — the select/aggregate core of the switch AR path (`jsq_route`'s
    fluid twin).  `q`/`cap`/`w`: (..., S) summed pair queue, path
    capacity, and path weight; returns (..., S) spine fractions."""
    up_mask = cap > 1e-9
    score = jsq_bin(q, nbins=nbins, qmax=qmax) / jnp.maximum(w, 1e-9)
    logit = jnp.where(up_mask, -score / temperature, -1e30)
    logit -= logit.max(-1, keepdims=True)
    e = jnp.exp(logit)
    sums = e.sum(-1, keepdims=True)
    return jnp.where(sums > 0, e / jnp.maximum(sums, 1e-30), 0.0)


def bottleneck_ref(cap, load, *, eps: float = 1e-12) -> jax.Array:
    """Per-link bottleneck scaling factor min(1, cap/load) — the fluid
    fair-share clamp applied after every load accumulation.  Elementwise
    and bit-identical to the engine's historical jnp math."""
    return jnp.minimum(1.0, cap / jnp.maximum(load, eps))


def bucket_sum_ref(g, *, ordered: bool = False) -> jax.Array:
    """Sum the trailing bucket-width axis of a gathered (..., rows, C)
    load plan.  `ordered=True` accumulates strictly left-to-right (flow
    order) — float64 parity mode, where a last-ulp tree-reduction
    difference vs NumPy's sequential `np.add.at` can walk a queue across
    an ECN threshold and fork the trajectory; `ordered=False` takes the
    fast tree reduction."""
    if ordered:
        return jax.lax.fori_loop(
            1, g.shape[-1],
            lambda c, acc: acc + jax.lax.dynamic_index_in_dim(
                g, c, g.ndim - 1, keepdims=False),
            g[..., 0])
    return g.sum(-1)


def load_bottleneck_ref(g, cap, *, eps: float = 1e-12,
                        ordered: bool = False):
    """Fused stage-A/stage-B load-accumulate + bottleneck: reduce a
    gathered (P, rows, C) plan to per-link loads and their scale
    factors.  Returns `(load, frac)`, both (P, rows)."""
    load = bucket_sum_ref(g, ordered=ordered)
    return load, bottleneck_ref(cap, load, eps=eps)


def queue_update_ref(q, load, cap, *, q_cap: float, eps: float = 1e-12):
    """Fluid queue integrator: one slot of (load - cap)/cap growth,
    clipped to [0, q_cap], dead links (cap <= eps) pinned to empty.
    Returns `(q_new, util)` with util = load/cap."""
    q_new = jnp.clip(q + (load - cap) / jnp.maximum(cap, eps),
                     0.0, q_cap)
    q_new = jnp.where(cap <= eps, 0.0, q_new)
    util = load / jnp.maximum(cap, eps)
    return q_new, util


def nic_update_ref(qmean, rate, alpha, esr, *, mode: str,
                   base_rtt_us: float, slot_us: float, ecn_thresh: float,
                   target_rtt_us: float, min_rate: float, md: float,
                   ai: float, rtt_gain: float, dcqcn_ai: float,
                   alpha_g: float):
    """Fused per-slot NIC control update: queue-derived RTT/ECN signals
    plus one step of the CC rate law for a static `mode`.  All inputs
    (F, P) except `esr` (F, 1) bool — ESR's extra multiplicative cut,
    only read by 'agg'.  Returns `(rtt, ecn, rate_new, alpha_new)`;
    alpha passes through untouched except under 'dcqcn'.

    mode:
      'spx'   — per-plane AIMD with ECN-proportional cut and RTT trim
                (also the swlb rate law; probe/eligibility bookkeeping
                stays in the engine).
      'dcqcn' — DCQCN: EWMA alpha, multiplicative cut on any-plane ECN.
      'agg'   — one aggregate context across planes ('global'/'esr').
    """
    rtt = base_rtt_us + qmean * slot_us * 0.5
    ecn = jnp.where(qmean > ecn_thresh,
                    jnp.minimum(1.0, qmean / (4 * ecn_thresh)), 0.0)
    if mode == "dcqcn":
        ecn_any = ecn.max(-1, keepdims=True)
        alpha_new = (1 - alpha_g) * alpha + alpha_g * (ecn_any > 0)
        cut = rate * (1 - alpha_new / 2)
        grow = jnp.minimum(rate + dcqcn_ai, 1.0)
        new = jnp.clip(jnp.where(ecn_any > 0, cut, grow), min_rate, 1.0)
        return rtt, ecn, new, alpha_new
    if mode == "agg":
        agg_ecn = ecn.max(-1, keepdims=True)
        agg_rtt = rtt.max(-1, keepdims=True)
        cut = rate * md
        rtt_err = (agg_rtt - target_rtt_us) / target_rtt_us
        trim = rate * (1 - rtt_gain * jnp.clip(rtt_err, 0, 2))
        grow = jnp.minimum(rate + ai, 1.0)
        new = jnp.where(agg_ecn > 0, cut,
                        jnp.where(rtt_err > 0.25, trim, grow))
        new = new * jnp.where(jnp.logical_and(esr, agg_ecn > 0),
                              0.85, 1.0)
        return rtt, ecn, jnp.clip(new, min_rate, 1.0), alpha
    if mode != "spx":
        raise ValueError(f"unknown nic-update mode {mode!r}")
    rtt_err = (rtt - target_rtt_us) / target_rtt_us
    cut = rate * (md + (1 - md) * jnp.clip(1 - ecn, 0, 1))
    trim = rate * (1 - rtt_gain * jnp.clip(rtt_err, 0, 2))
    grow = jnp.minimum(rate + ai, 1.0)
    new = jnp.clip(
        jnp.where(ecn > 0, cut, jnp.where(rtt_err > 0.25, trim, grow)),
        min_rate, 1.0)
    return rtt, ecn, new, alpha


def int8_encode_ref(x, noise):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale + noise), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def int8_decode_ref(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)
