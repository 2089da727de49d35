"""Pytree carries for the JAX engine.

`NamedTuple`s so everything is a pytree for free: `FlowBatch` is the
static flow population (one leading batch axis when vmapped), `NicCarry`
mirrors `netsim.cc.NicState`'s mutable arrays, and `SimCarry` is the full
`lax.scan` carry — fabric queues, NIC state, transfer progress, and the
post-warmup goodput accumulator that replaces the NumPy backend's dense
`(T, F)` recording.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np


class FlowBatch(NamedTuple):
    src: jnp.ndarray           # (F,) int
    dst: jnp.ndarray           # (F,) int
    src_leaf: jnp.ndarray      # (F,) int
    dst_leaf: jnp.ndarray      # (F,) int
    demand: jnp.ndarray        # (F,) float
    bytes_total: jnp.ndarray   # (F,) float (inf = open-loop)
    start_slot: jnp.ndarray    # (F,) int
    same_leaf: jnp.ndarray     # (F,) bool
    phase: jnp.ndarray         # (F,) int demand-timeline lane


class NicCarry(NamedTuple):
    rate: jnp.ndarray          # (F, P) allowances
    alpha: jnp.ndarray         # (F, P) dcqcn alpha
    probe_miss: jnp.ndarray    # (F, P) int
    eligible: jnp.ndarray      # (F, P) bool
    pending_fail: jnp.ndarray  # (F, P) int (swlb delayed reaction)


class SimCarry(NamedTuple):
    """Stage-A queues (`q_up`/`q_down`) are leaf↔spine on leaf_spine
    and leaf↔agg on fat_tree; stage-B queues (`q2_up`/`q2_down`) are
    the fat-tree pod↔core tier — (P, 1, 1) placeholders on leaf_spine,
    never read there (the topology kind is static at trace time)."""
    q_up: jnp.ndarray          # (P, L, S|A) queue, slot*cap units
    q_down: jnp.ndarray        # (P, S|A, L)
    q2_up: jnp.ndarray         # (P, pods, C) fat_tree; (P, 1, 1) else
    q2_down: jnp.ndarray       # (P, pods, C) fat_tree; (P, 1, 1) else
    nic: NicCarry
    remaining: jnp.ndarray     # (F,)
    done: jnp.ndarray          # (F,) bool
    completion: jnp.ndarray    # (F,) int, -1 = unfinished
    goodput_sum: jnp.ndarray   # (F,) sum of achieved over counted frames
    util_up: jnp.ndarray       # (P, L, S|A) last slot's uplink utilization


def stage_shapes(cfg) -> Tuple[Tuple[int, int, int], Tuple[int, int, int]]:
    """((P, L, n_up), (P, pods_b, cores_b)) queue/capacity shapes for a
    `JxConfig`-like object — the single source of truth of the carry's
    queue shapes."""
    P, L = cfg.n_planes, cfg.n_leaves
    if cfg.kind == "fat_tree":
        return (P, L, cfg.n_aggs), (P, cfg.n_pods, cfg.n_cores)
    return (P, L, cfg.n_spines), (P, 1, 1)


def probe_miss_dtype(cfg, float_dtype) -> jnp.dtype:
    """int8 under the compact carry (float32 runs only — the probe
    counter saturates at `probe_timeout`, far inside int8 range); the
    default integer width otherwise.  Read by the megabatch host-side
    carry builder."""
    if (getattr(cfg, "compact_carry", False)
            and jnp.dtype(float_dtype) == jnp.float32):
        return jnp.dtype(jnp.int8)
    return jnp.asarray(np.int64(0)).dtype
