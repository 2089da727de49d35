"""Jitted slot loop: the JAX twin of `netsim.sim.run_sim`.

One slot is a pure function `(SimCarry, slot inputs) -> SimCarry` that
reproduces, operation for operation, the NumPy pipeline:

  PLB plane split -> routing fractions (AR / weighted-AR from the queue
  carry, ECMP from precompiled assignment segments) -> per-link bottleneck
  scaling -> queue/ECN/RTT evolution -> NIC control update
  (`spx|dcqcn|global|esr|swlb`) -> loss-stall masking -> transfer
  completion.

The loop runs under `lax.scan`; whole sweep axes (seeds, each with its own
flow population and fault timeline) run as one `jax.vmap` batch.  Fault
schedules are compiled to capacity-multiplier timelines by `events.py` and
enter the scan compressed to their piecewise-constant segment snapshots
(per-slot segment-id gathers re-expand them); ECMP spine assignments
arrive as step-function segments precomputed by
`events.ecmp_assign_segments` (the dead-path re-hash depends only on the
static timeline, so its RNG stream is replayed exactly on the host).

Routing and NIC control exist in two dispatch forms sharing one set of
branch functions:

  * **static** — `cfg.routing`/`cfg.nic` are concrete strings and the
    branch is chosen at trace time (the historical per-group path: one
    compiled program per (scenario, routing, nic) structure);
  * **traced** — `cfg.routing == cfg.nic == "*"` and a per-batch-element
    `StackIdx` selects the branch via `lax.switch` inside the traced
    program, so a whole routing × nic × fault × seed grid runs as ONE
    compiled program (`megabatch.py` builds those batches).

The per-slot hot paths dispatch through the `repro.kernels` package —
NIC plane split (`plb_select.plane_split`), quantized-JSQ spine scoring
(`jsq_route.pair_fractions`), fused load-accumulate + bottleneck
(`link_load.bucket_load_bottleneck` / `link_load.bottleneck`), and the
fused queue/ECN/NIC control update (`queue_ecn.queue_update` /
`queue_ecn.nic_update`): a Pallas kernel on TPU (or under
`REPRO_NETSIM_PALLAS=1`, interpret mode off-TPU), and otherwise a jnp
fallback (`kernels/ref.py`) that is bit-identical to the historical
engine math.  On a TPU, leaf-spine ECMP looks up each flow's path links
by an exact one-hot contraction on the MXU instead of point gathers
(`ecmp_onehot`), and the sparse path sums its ECMP link loads and
host-port loads by contracting the same leaf one-hots instead of
scatter-adding (`onehot_agg`).

Flow aggregation has two modes (`JxConfig.agg_mode`): **dense** gathers
flows into padded per-link bucket matrices (fast at registry shapes,
but memory is bounded by `leaves² · planes`-sized plans), **sparse**
accumulates with `segment_sum` keyed by (plane, link) so flow count
bounds memory — the giga-scale path, selected automatically for large
fabrics or forced with `REPRO_JX_AGG=dense|sparse`.  On XLA CPU f64 the
sparse scatter applies updates in flow order, matching the NumPy
engine's sequential `np.add.at` bit for bit.

With x64 enabled the trajectory matches the NumPy backend within 1e-5
(registry-wide parity is enforced by `tests/test_jx_parity.py`); without
x64 it runs float32 — faster, looser tolerance (and
`REPRO_JX_COMPACT=1` additionally shrinks the scan carry: int8 probe
counters).
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, replace
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import pallas_enabled
from repro.kernels.jsq_route import pair_fractions as _k_pair_fractions
from repro.kernels.link_load import (bottleneck as _k_bottleneck,
                                     bucket_load_bottleneck,
                                     segment_load)
from repro.kernels.plb_select import plane_split as _k_plane_split
from repro.kernels.queue_ecn import (nic_update as _k_nic_update,
                                     queue_update as _k_queue_update)
from repro.netsim.cc import (DCQCN_AI, DCQCN_ALPHA_G, MIN_RATE,
                             PROBE_TIMEOUT, SPX_AI, SPX_MD, SPX_RTT_GAIN,
                             TARGET_RTT_US)
from repro.netsim.fabric import (AR_TEMPERATURE, ECN_QUEUE_THRESH,
                                 JSQ_BINS, Q_CAP, FlowArrays)
# the counters' public names stay importable from the engine
from repro.netsim.flight import (collect_dispatch,  # noqa: F401
                                 dispatch_stats, record_launch,
                                 reset_dispatch_stats, watch_compiles)
from repro.netsim.sim import SimConfig
from repro.trace import TraceSpec

from .events import (FaultTimeline, compile_fault_timeline,
                     ecmp_assign_segments, lagged_timeline)
from .state import FlowBatch, NicCarry, SimCarry, init_carry

_EPS = 1e-12

# flipped on first dispatch; scenarios.runner consults it to decide
# whether forking a process pool is still safe in this process
_BACKEND_USED = False


def _env_flag(name: str) -> Optional[bool]:
    env = os.environ.get(name)
    if env is None:
        return None
    return env.lower() in ("1", "true", "t", "yes", "y", "on")


def agg_mode_default(n_hosts: int, n_leaves: int, n_paths: int,
                     n_planes: int) -> str:
    """Pick the flow-aggregation mode for a fabric shape.  Dense
    gather-plan bucket sums win at registry shapes (XLA CPU gathers beat
    scatters by ~10x), but their ECMP plans are `2·L²·paths·planes`
    int32 rows per capacity segment — at giga-scale that term, not the
    flow population, dominates memory.  `REPRO_JX_AGG=dense|sparse`
    overrides."""
    env = os.environ.get("REPRO_JX_AGG")
    if env in ("dense", "sparse"):
        return env
    big = (n_hosts >= 4096 or
           n_leaves * n_leaves * n_paths * n_planes > (1 << 22))
    return "sparse" if big else "dense"


def compact_carry_default() -> bool:
    """`REPRO_JX_COMPACT=1` opts float32 runs into the shrunken scan
    carry (int8 probe counters; x64 parity runs always keep wide
    state)."""
    return bool(_env_flag("REPRO_JX_COMPACT"))


# a v5e's HBM
_HBM_BYTES = 16 * 10**9


def ecmp_onehot_budget(platform: str) -> int:
    """Bytes that one `(F, L)` bfloat16 one-hot of the ECMP link lookups
    may take on `platform`, or 0 to keep the point gathers (see
    `ecmp_onehot`).  On a TPU v5e the gather unit serves `_route_ecmp`'s
    per-flow point gathers at about 12 ns an element while the MXU
    idles, and a one-hot contraction gives the same values exactly
    (`_onehot_lookup`).  XLA CPU runs one-hot matmuls about 10x slower
    than gathers, so every other platform keeps the gathers.  A batch
    element may hold two one-hots (source and destination leaf) through
    the whole scan, and each may take 1/64 of a v5e's 16 GB: F·L·2 bytes
    up to 250 MB.  131,072 flows on 256 leaves take 64 MB; the guard
    binds from about 490,000 flows on 256 leaves."""
    return _HBM_BYTES // 64 if platform == "tpu" else 0


# per-flow working-set arrays live per (flow, plane) cell in the chunked
# estimate: 5 NicCarry leaves + offered/fabric_rate/through/qmean/
# achieved_pp/rtt/ecn intermediates
_FLOW_WORKING_ARRAYS = 12


def flow_chunk_default(n_flows: int, n_planes: int,
                       agg_mode: str) -> int:
    """Chunk length for streaming the flow axis through `_slot_step`'s
    sparse path, or 0 to keep the monolithic layout.  Auto-enables when
    the per-flow working set (roughly `_FLOW_WORKING_ARRAYS` live
    (F, P) arrays) exceeds `REPRO_JX_FLOW_BUDGET_MB` (default 8192 —
    one device's comfortable share); `REPRO_JX_FLOW_CHUNK=<n>` forces a
    chunk length (0 disables) regardless of the budget.  Chunking is a
    sparse-aggregation feature: callers that enable it coerce
    `agg_mode="sparse"` (the dense gather plans are exactly the
    monolithic layout chunking exists to avoid)."""
    env = os.environ.get("REPRO_JX_FLOW_CHUNK")
    if env is not None:
        return max(0, int(env))
    if agg_mode != "sparse" or n_flows <= 0:
        return 0
    itemsize = 8 if jax.config.jax_enable_x64 else 4
    per_flow = max(1, n_planes) * itemsize * _FLOW_WORKING_ARRAYS
    budget = float(os.environ.get("REPRO_JX_FLOW_BUDGET_MB", 8192))
    if n_flows * per_flow <= budget * 2**20:
        return 0
    chunk = int(budget * 2**20 // per_flow)
    # pow2 floor (shape-bucket friendly), never below 1024 — tiny
    # chunks would make the inner scan longer than the flow axis wins
    chunk = max(1024, 1 << max(0, chunk.bit_length() - 1))
    return min(chunk, n_flows)


@dataclass(frozen=True)
class JxConfig:
    """Static (hashable) simulation parameters: everything `lax.scan`
    needs resolved at trace time — sim knobs, topology shape, and the
    `FluidFabric` constants.  `routing`/`nic` of `"*"` mean "traced":
    the slot step expects a per-element `StackIdx` and selects the
    branch with `lax.switch` (megabatch mode)."""
    slots: int
    slot_us: float
    routing: str
    nic: str
    base_rtt_us: float
    warmup_frac: float
    record_every: int
    sw_lb_delay_slots: int
    n_planes: int
    n_leaves: int
    n_spines: int
    n_hosts: int
    uplink_cap: float
    access_cap: float
    kind: str = "leaf_spine"
    n_pods: int = 1
    n_aggs: int = 1
    n_cores: int = 1
    core_cap: float = 1.0
    target_rtt_us: float = TARGET_RTT_US
    probe_timeout: int = PROBE_TIMEOUT
    ecn_queue_thresh: float = ECN_QUEUE_THRESH
    ar_temperature: float = AR_TEMPERATURE
    jsq_bins: int = JSQ_BINS
    q_cap: float = Q_CAP
    use_pallas: bool = False
    # "dense": padded gather-plan bucket sums (registry shapes);
    # "sparse": segment_sum keyed by (plane, link), so flow count — not
    # leaves²·paths·planes — bounds memory (giga-scale shapes).
    agg_mode: str = "dense"
    # float32 runs only: int8 probe counters in the scan carry
    compact_carry: bool = False
    # Sparse mode only: >0 streams the flow axis through the slot step
    # in chunks of this length (an inner `lax.scan` accumulates the
    # per-chunk scatter-adds in flow order, so x64 results stay
    # bit-identical to the monolithic layout) — populations larger than
    # one device's memory budget still run.  0 = monolithic (see
    # `flow_chunk_default`).
    flow_chunk: int = 0
    # Schedule workloads: number of demand-multiplier lanes in the
    # per-segment phase timeline (0 = no timeline; the multiply is
    # compiled out and program identity matches pre-schedule HLO).
    n_phases: int = 0
    # Failure reaction (spec.reaction enabled): routing steers against
    # the four extra *visible*-capacity operands (the lagged timeline)
    # and every slot additionally emits the blackholed-byte total.
    # False leaves those operands dead ones-dummies and the scan ys a
    # raw scalar — the traced program is the pre-reaction one.  The
    # detect/converge depths and the reroute mode stay host-side (they
    # only shape the operand *values*), so a mode × detect sweep shares
    # one compiled program per bucket.
    react: bool = False
    # Leaf-spine ECMP: the largest `(F, L)` bfloat16 one-hot, in bytes,
    # that the per-flow link lookups contract against on the MXU; 0
    # keeps the point gathers (`ecmp_onehot_budget`, `ecmp_onehot`).
    ecmp_onehot_bytes: int = 0
    # Participates in every jit-cache key / launch fingerprint, so the
    # default (disabled) spec leaves program identity — and the HLO —
    # exactly as if tracing did not exist.
    trace: TraceSpec = TraceSpec()

    @property
    def n_paths(self) -> int:
        """Per-(leaf pair, plane) routing-choice axis: spines on
        leaf_spine, cores on fat_tree."""
        return self.n_spines if self.kind == "leaf_spine" else self.n_cores

    @property
    def n_up(self) -> int:
        """Stage-A link axis per leaf: spines or pod-local aggs."""
        return self.n_spines if self.kind == "leaf_spine" else self.n_aggs

    @property
    def cores_per_agg(self) -> int:
        return self.n_cores // self.n_aggs

    @property
    def leaves_per_pod(self) -> int:
        return self.n_leaves // self.n_pods

    @classmethod
    def from_sim(cls, cfg: SimConfig, topo) -> "JxConfig":
        """`topo` is a `TopologySpec` (or anything with the same shape
        attributes and a uniform base capacity)."""
        kind = getattr(topo, "kind", "leaf_spine")
        fat = kind == "fat_tree"
        return cls(
            slots=cfg.slots, slot_us=cfg.slot_us, routing=cfg.routing,
            nic=cfg.nic, base_rtt_us=cfg.base_rtt_us,
            warmup_frac=cfg.warmup_frac, record_every=cfg.record_every,
            sw_lb_delay_slots=cfg.sw_lb_delay_slots(),
            n_planes=topo.n_planes, n_leaves=topo.n_leaves,
            n_spines=topo.n_spines, n_hosts=topo.n_hosts,
            uplink_cap=topo.link_cap * topo.parallel_links,
            access_cap=topo.access_cap,
            kind=kind,
            n_pods=topo.n_pods if fat else 1,
            n_aggs=topo.n_aggs if fat else 1,
            n_cores=topo.n_cores if fat else 1,
            core_cap=topo.core_cap if fat else 1.0,
            use_pallas=pallas_enabled(),
            agg_mode=agg_mode_default(
                topo.n_hosts, topo.n_leaves,
                topo.n_cores if fat else topo.n_spines, topo.n_planes),
            compact_carry=compact_carry_default(),
            ecmp_onehot_bytes=ecmp_onehot_budget(jax.default_backend()),
            trace=getattr(cfg, "trace", TraceSpec()))


def ecmp_onehot(cfg: JxConfig, n_flows: int) -> bool:
    """Whether `_route_ecmp` looks its links up by one-hot contraction
    for a batch element of `n_flows` flows: a leaf-spine fabric, the
    monolithic flow axis, and an `(F, L)` bfloat16 one-hot within
    `cfg.ecmp_onehot_bytes`.  Decided from static shapes alone, at trace
    time."""
    return (cfg.kind == "leaf_spine" and not cfg.flow_chunk
            and 0 < n_flows * cfg.n_leaves * 2 <= cfg.ecmp_onehot_bytes)


def onehot_agg(cfg: JxConfig, n_flows: int) -> bool:
    """Whether a batch element of `n_flows` flows sums its sparse link
    loads (ECMP) and host-port loads by contracting the same leaf
    one-hots on the MXU (`_leaf_sum`) instead of scatter-adding
    (`segment_load`): the one-hots' own rule (`ecmp_onehot`), sparse
    aggregation, and a float32 program (under x64 the scatter keeps the
    NumPy engine's flow order).  On a TPU v5e the scatter-adds run at
    about 2 ms a megabyte of operand, while the contraction's products
    are exact and each bucket stays a float32 sum.  Decided from static
    shapes and the platform alone, at trace time."""
    return (cfg.agg_mode == "sparse" and not jax.config.jax_enable_x64
            and ecmp_onehot(cfg, n_flows))


@dataclass
class JxSimResult:
    """Distilled run output — the fields `scenarios.runner` consumes.
    Unlike the NumPy `SimResult` there is no dense `(T, F)` goodput
    record; the per-flow mean and the per-slot total are accumulated
    inside the scan instead."""
    mean_goodput: np.ndarray     # (F,) post-warmup average
    completion_slot: np.ndarray  # (F,) -1 = unfinished
    total_goodput: np.ndarray    # (T_rec,) summed over flows per frame
    util_up_last: np.ndarray     # (P, L, S)
    groups: List[str]
    group_of: np.ndarray
    slot_us: float
    trace: Optional[Dict[str, np.ndarray]] = None
    # failure reaction only: full-rate (T,) per-slot bytes offered onto
    # physically dead paths (None when spec.reaction is off)
    blackhole_timeline: Optional[np.ndarray] = None

    def group_mean(self, group: str) -> float:
        gi = self.groups.index(group)
        return float(self.mean_goodput[self.group_of == gi].mean())


# ---------------------------------------------------------------------------
# traced branch selection (megabatch mode)
# ---------------------------------------------------------------------------

ROUTE_PAIR, ROUTE_ECMP = 0, 1
_SPLIT_MODE = {"spx": "spx", "dcqcn": "dcqcn", "global": "agg",
               "esr": "agg", "swlb": "swlb"}
_BRANCH_ORDER = ("spx", "dcqcn", "agg", "swlb")
_BRANCH_IDX = {m: i for i, m in enumerate(_BRANCH_ORDER)}


class StackIdx(NamedTuple):
    """Per-batch-element (routing, nic) branch selectors for the traced
    dispatch form — scalars under `vmap`, arrays `(B,)` host-side.  The
    one `nic` index selects both the plane-split and the control-update
    branch (their branch lists share `_BRANCH_ORDER`)."""
    route: jnp.ndarray    # 0 = pair (ar/war), 1 = ecmp
    is_war: jnp.ndarray   # bool: fold remote weights into pair scores
    nic: jnp.ndarray      # _BRANCH_ORDER index (split + update)
    is_esr: jnp.ndarray   # bool: ESR's extra multiplicative cut


def stack_idx_for(routing: str, nic: str) -> Tuple[int, bool, int, bool]:
    """Host-side `StackIdx` row for one grid point."""
    return (ROUTE_ECMP if routing == "ecmp" else ROUTE_PAIR,
            routing == "war", _BRANCH_IDX[_SPLIT_MODE[nic]],
            nic == "esr")


# ---------------------------------------------------------------------------
# dispatch bookkeeping: launches + (program-level) compiles, host spans
# and counters live in `repro.netsim.flight`; the engine fingerprints its
# programs and counts JAX's compiles from its first import on
# ---------------------------------------------------------------------------

_JIT_CACHE: Dict[Tuple, Callable] = {}
watch_compiles()


def _device_fingerprint() -> Tuple:
    """Identity of the visible device set — part of every jit-cache and
    program key, so a `pmap` built for N host devices is never reused
    after the device set changes."""
    return tuple((d.platform, d.id) for d in jax.devices())


def _record_launch(tag: str, key, args) -> None:
    shapes = tuple(
        (np.shape(leaf), str(getattr(leaf, "dtype", type(leaf))))
        for leaf in jax.tree_util.tree_leaves(args))
    record_launch((tag, key, shapes, bool(jax.config.jax_enable_x64),
                   _device_fingerprint()))


# ---------------------------------------------------------------------------
# NIC: plane split + control update (port of netsim.cc.NicState)
# ---------------------------------------------------------------------------

def _split_mode(cfg: JxConfig, mode: str, nic: NicCarry,
                demand: jnp.ndarray) -> jnp.ndarray:
    """One plane-split branch — the select stage of the paper's NIC PLB
    (Fig. 4), dispatched through the kernels layer."""
    return _k_plane_split(nic.rate, nic.eligible, demand, mode=mode,
                          min_rate=MIN_RATE, use_pallas=cfg.use_pallas)


def _plane_split(cfg: JxConfig, nic: NicCarry, demand: jnp.ndarray,
                 stack: Optional[StackIdx] = None) -> jnp.ndarray:
    if stack is None:
        return _split_mode(cfg, _SPLIT_MODE[cfg.nic], nic, demand)
    return jax.lax.switch(
        stack.nic,
        [partial(_split_mode, cfg, m, nic, demand)
         for m in _BRANCH_ORDER])


def _probe_common(cfg: JxConfig, nic: NicCarry, probe_ok: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    miss = ~probe_ok
    # saturate at the timeout: `dead` is unchanged (>= comparison) and
    # the counter stays in int8 range under the compact carry
    bump = jnp.minimum(nic.probe_miss + 1, cfg.probe_timeout)
    probe_miss = jnp.where(miss, bump, 0).astype(nic.probe_miss.dtype)
    dead = probe_miss >= cfg.probe_timeout
    return probe_miss, dead


def _probe_basic(cfg: JxConfig, nic: NicCarry, rate: jnp.ndarray,
                 probe_ok: jnp.ndarray, slot: jnp.ndarray) -> NicCarry:
    probe_miss, dead = _probe_common(cfg, nic, probe_ok)
    was = nic.eligible
    eligible = ~dead
    just_back = eligible & ~was
    rate = jnp.where(just_back, 0.5, rate)
    rate = jnp.where(~eligible, MIN_RATE, rate)
    return NicCarry(rate=rate, alpha=nic.alpha, probe_miss=probe_miss,
                    eligible=eligible, pending_fail=nic.pending_fail)


def _probe_swlb(cfg: JxConfig, nic: NicCarry, rate: jnp.ndarray,
                probe_ok: jnp.ndarray, slot: jnp.ndarray) -> NicCarry:
    if cfg.sw_lb_delay_slots <= 0:
        return _probe_basic(cfg, nic, rate, probe_ok, slot)
    probe_miss, dead = _probe_common(cfg, nic, probe_ok)
    eligible, pending = nic.eligible, nic.pending_fail
    newly = dead & eligible & (pending == 0)
    pending = jnp.where(newly, slot + cfg.sw_lb_delay_slots, pending)
    fire = (pending > 0) & (slot >= pending)
    eligible = jnp.where(fire & dead, False, eligible)
    healed = ~dead & ~eligible
    eligible = jnp.where(healed, True, eligible)
    pending = jnp.where(~dead, 0, pending)
    rate = jnp.where(~eligible, MIN_RATE, rate)
    return NicCarry(rate=rate, alpha=nic.alpha, probe_miss=probe_miss,
                    eligible=eligible, pending_fail=pending)


def _upd_rate(cfg: JxConfig, mode: str, nic: NicCarry, qmean, esr):
    """RTT/ECN derivation + one fused CC rate branch, dispatched through
    `kernels.queue_ecn.nic_update` (Pallas on TPU, bit-exact jnp ref
    otherwise).  Returns `(rtt, ecn, rate, alpha)`."""
    return _k_nic_update(
        qmean, nic.rate, nic.alpha, esr, mode=mode,
        base_rtt_us=cfg.base_rtt_us, slot_us=cfg.slot_us,
        ecn_thresh=cfg.ecn_queue_thresh,
        target_rtt_us=cfg.target_rtt_us, min_rate=MIN_RATE, md=SPX_MD,
        ai=SPX_AI, rtt_gain=SPX_RTT_GAIN, dcqcn_ai=DCQCN_AI,
        alpha_g=DCQCN_ALPHA_G, use_pallas=cfg.use_pallas)


def _upd_dcqcn(cfg, nic, qmean, probe_ok, slot, esr):
    rtt, ecn, rate, alpha = _upd_rate(cfg, "dcqcn", nic, qmean, esr)
    return nic._replace(rate=rate, alpha=alpha), rtt, ecn


def _upd_agg(cfg, nic, qmean, probe_ok, slot, esr):
    """'global'/'esr': one aggregate CC context across planes.  ESR's
    extra multiplicative cut rides the kernel's `esr` operand — a ×1.0
    multiply for non-ESR flows, which is bit-exact."""
    rtt, ecn, rate, _ = _upd_rate(cfg, "agg", nic, qmean, esr)
    return _probe_basic(cfg, nic, rate, probe_ok, slot), rtt, ecn


def _upd_spx(cfg, nic, qmean, probe_ok, slot, esr):
    rtt, ecn, rate, _ = _upd_rate(cfg, "spx", nic, qmean, esr)
    return _probe_basic(cfg, nic, rate, probe_ok, slot), rtt, ecn


def _upd_swlb(cfg, nic, qmean, probe_ok, slot, esr):
    # swlb shares spx's per-plane AIMD law; only the probe path differs
    rtt, ecn, rate, _ = _upd_rate(cfg, "spx", nic, qmean, esr)
    return _probe_swlb(cfg, nic, rate, probe_ok, slot), rtt, ecn


def _nic_update(cfg: JxConfig, nic: NicCarry, qmean: jnp.ndarray,
                probe_ok: jnp.ndarray, slot: jnp.ndarray,
                stack: Optional[StackIdx] = None
                ) -> Tuple[NicCarry, jnp.ndarray, jnp.ndarray]:
    """NIC control update (pre-stall rates, as in `run_sim`), fused with
    the rtt/ecn derivation from the per-flow mean queue.  Returns the
    new carry plus rtt/ecn (for the queue-delay estimate and trace)."""
    F = qmean.shape[0]
    if stack is None:
        esr = jnp.full((F, 1), cfg.nic == "esr")
        if cfg.nic == "dcqcn":
            return _upd_dcqcn(cfg, nic, qmean, probe_ok, slot, esr)
        if cfg.nic in ("global", "esr"):
            return _upd_agg(cfg, nic, qmean, probe_ok, slot, esr)
        if cfg.nic == "swlb":
            return _upd_swlb(cfg, nic, qmean, probe_ok, slot, esr)
        return _upd_spx(cfg, nic, qmean, probe_ok, slot, esr)
    esr = jnp.broadcast_to(jnp.reshape(stack.is_esr, (1, 1)), (F, 1))
    return jax.lax.switch(stack.nic, [
        partial(_upd_spx, cfg, nic, qmean, probe_ok, slot, esr),
        partial(_upd_dcqcn, cfg, nic, qmean, probe_ok, slot, esr),
        partial(_upd_agg, cfg, nic, qmean, probe_ok, slot, esr),
        partial(_upd_swlb, cfg, nic, qmean, probe_ok, slot, esr),
    ])


# ---------------------------------------------------------------------------
# routing fractions (port of FluidFabric.pair_fractions / ecmp_fractions)
# ---------------------------------------------------------------------------

def _pair_fractions(cfg: JxConfig, q_up: jnp.ndarray, q_down: jnp.ndarray,
                    up: jnp.ndarray, down: jnp.ndarray,
                    remote_weights: Optional[jnp.ndarray]) -> jnp.ndarray:
    """(P, L_src, L_dst, S) spine split; 'war' folds in remote weights.
    Scoring + softmax run through `kernels.jsq_route.pair_fractions`."""
    cap = jnp.minimum(up[:, :, None, :],
                      jnp.swapaxes(down, 1, 2)[:, None, :, :])
    q = (q_up[:, :, None, :] +
         jnp.swapaxes(q_down, 1, 2)[:, None, :, :])
    w = cap
    if remote_weights is not None:
        w = w * jnp.swapaxes(remote_weights, 1, 2)[:, None, :, :]
    return _k_pair_fractions(q, cap, w, nbins=cfg.jsq_bins,
                             temperature=cfg.ar_temperature, qmax=8.0,
                             use_pallas=cfg.use_pallas)


def _bottleneck(cfg: JxConfig, up, down, load_up, load_down):
    f_up = _k_bottleneck(up, load_up, eps=_EPS,
                         use_pallas=cfg.use_pallas)
    f_down = _k_bottleneck(down, load_down, eps=_EPS,
                           use_pallas=cfg.use_pallas)
    return f_up, f_down


# ---------------------------------------------------------------------------
# one slot
# ---------------------------------------------------------------------------

class _AggPerms(NamedTuple):
    """Flow -> bucket aggregation plans.  XLA CPU scatters (and one-hot
    matmuls) are an order of magnitude slower than gathers, so every
    per-slot "sum flows into buckets" becomes: gather flows into a
    `(n_buckets, width)` layout (rows padded with an index that reads a
    zero row) and sum the width axis.  The permutations are static per
    run — ECMP's spine assignment is piecewise-constant, so it gets one
    plan per capacity segment.

    The ECMP plan (`ecmp_load`) stacks uplink and downlink buckets into
    one `(n_seg, P, _plan_rows(cfg), C)` matrix — stage-A up/down
    buckets, plus the two stage-B (pod–core) bucket families on
    fat_tree.  In float64 (parity mode) its
    width axis is summed strictly left-to-right (flow order): those sums
    feed the queue integrators, where a last-ulp tree-reduction
    difference vs NumPy's sequential `np.add.at` can walk a queue across
    an ECN threshold and fork the trajectory.  Float32 runs take the
    fast tree reduction instead — they drift from the f64 reference at
    ulp level regardless.  AR/WAR fractions are smooth in the loads, so
    their aggregations tolerate tree reduction at either precision."""
    src: jnp.ndarray        # (H, Cs)  flows by src host
    dst: jnp.ndarray        # (H, Cd)  flows by dst host
    pair: jnp.ndarray       # (L*L, Cp) flows by (src_leaf, dst_leaf)
    ecmp_load: jnp.ndarray  # (n_seg, P, L*S + S*L, Cu)


def _perm_matrix(keys: np.ndarray, n_buckets: int, width: int,
                 pad: int) -> np.ndarray:
    """(n_buckets, width) flow indices grouped by key, flow order
    preserved within a bucket, padded with `pad`."""
    perm = np.full((n_buckets, width), pad, np.int32)
    keys = np.asarray(keys)
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    counts = np.bincount(sk, minlength=n_buckets)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    ranks = np.arange(len(sk)) - starts[sk]
    perm[sk, ranks] = order
    return perm


def _seg_sum(vals: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """vals (F, P), perm (K, C) -> (K, P) bucket sums."""
    pad = jnp.concatenate(
        [vals, jnp.zeros((1, vals.shape[1]), vals.dtype)], 0)
    return pad[perm].sum(1)


def _host_sum(cfg: JxConfig, vals: jnp.ndarray, idx: jnp.ndarray,
              perm: jnp.ndarray,
              onehot: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(F, P) per-flow values summed into (H, P) per-host buckets:
    gather-plan sum (dense) or a (host, plane)-keyed `segment_load`
    (sparse — the row-major flatten scatters in flow order, so XLA CPU
    f64 stays bit-equal to the NumPy engine's `np.add.at`).  With the
    (F, L) one-hot of the hosts' leaves (`onehot_agg`), each flow's value
    goes to the column of its host's place on the leaf, and `_leaf_sum`
    adds them up by leaf: host `h` is place `h % HPL` of leaf `h // HPL`,
    HPL hosts a leaf."""
    if cfg.agg_mode != "sparse":
        return _seg_sum(vals, perm)
    P = vals.shape[1]
    if onehot is not None:
        hpl = cfg.n_hosts // cfg.n_leaves
        place = (idx % hpl)[:, None, None] == jnp.arange(hpl)[:, None]
        v = jnp.where(place, vals[:, None, :], 0.0)          # (F, HPL, P)
        return _leaf_sum(onehot, v.reshape(-1, hpl * P)).reshape(
            cfg.n_hosts, P)
    keys = idx[:, None] * P + jnp.arange(P)[None, :]
    return segment_load(vals, keys, cfg.n_hosts * P).reshape(
        cfg.n_hosts, P)


def _path_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last (path) axis.  In float64 (parity mode) the adds
    follow NumPy's pairwise order for a contiguous axis — eight strided
    accumulators combined as a tree, then the tail in sequence, halves
    above 128 — because these sums scale whole transfers: a path-weighted
    scale that lands one ulp under 1.0 leaves a residue in `remaining`
    and finishes an exact-size transfer a slot late.  Float32 runs keep
    XLA's own reduction."""
    n = x.shape[-1]
    if x.dtype != jnp.float64:
        return x.sum(-1)
    if n > 128:
        h = n // 2
        h -= h % 8
        return _path_sum(x[..., :h]) + _path_sum(x[..., h:])
    if n < 8:
        out = x[..., 0]
        for i in range(1, n):
            out = out + x[..., i]
        return out
    m = n - n % 8
    r = [x[..., j] for j in range(8)]
    for i in range(8, m, 8):
        r = [r[j] + x[..., i + j] for j in range(8)]
    out = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
    for i in range(m, n):
        out = out + x[..., i]
    return out


def _pair_rate_sum(cfg: JxConfig, fabric_rate: jnp.ndarray,
                   pair_idx: jnp.ndarray,
                   aggs: "_AggPerms") -> jnp.ndarray:
    """(P, L, L) offered rate summed by (src-leaf, dst-leaf) pair."""
    P, L = cfg.n_planes, cfg.n_leaves
    if cfg.agg_mode != "sparse":
        return _seg_sum(fabric_rate, aggs.pair).T.reshape(P, L, L)
    keys = jnp.arange(P)[None, :] * (L * L) + pair_idx[:, None]
    return segment_load(fabric_rate, keys, P * L * L).reshape(P, L, L)


def _route_pair(cfg: JxConfig, carry: SimCarry, fabric_rate: jnp.ndarray,
                up: jnp.ndarray, down: jnp.ndarray, upv: jnp.ndarray,
                downv: jnp.ndarray, aggs: _AggPerms,
                pair_idx: jnp.ndarray, use_war):
    """AR / weighted-AR: leaf-pair spine fractions.  `use_war` is a
    Python bool on the static path or a traced bool under switch — the
    traced form multiplies weights by exactly 1.0 for plain AR, which is
    bit-identical to not multiplying.  `upv`/`downv` are the routing-
    *visible* capacities (the reaction-lagged view; the physical arrays
    themselves when reaction is off): fractions and remote weights steer
    against them, while loads/bottlenecks/queues stay physical — exactly
    `FluidFabric`'s `route_topo` split."""
    P, L = cfg.n_planes, cfg.n_leaves
    rw_arr = downv / jnp.maximum(downv.max(axis=1, keepdims=True), 1e-9)
    if isinstance(use_war, bool):
        rw = rw_arr if use_war else None
    else:
        rw = jnp.where(use_war, rw_arr, jnp.ones_like(downv))
    pair = _pair_fractions(cfg, carry.q_up, carry.q_down, upv, downv, rw)
    rate_pair = _pair_rate_sum(cfg, fabric_rate, pair_idx, aggs)
    load_up = jnp.einsum("plm,plms->pls", rate_pair, pair)
    load_down = jnp.einsum("plm,plms->psm", rate_pair, pair)
    f_up, f_down = _bottleneck(cfg, up, down, load_up, load_down)
    scale_pair = jnp.minimum(
        f_up[:, :, None, :],
        f_down.transpose(0, 2, 1)[:, None, :, :])         # (P, L, L, S)
    path_scale = _path_sum(pair * scale_pair).reshape(P, L * L)
    through = fabric_rate * path_scale[:, pair_idx].T
    q_pair = (carry.q_up[:, :, None, :] +
              carry.q_down.transpose(0, 2, 1)[:, None, :, :])
    qmean = _path_sum(pair * q_pair).reshape(P, L * L)[:, pair_idx].T
    if not cfg.react:
        return load_up, load_down, through, qmean
    # blackholed bytes: offered rate steered (by the lagged view) onto
    # physically dead paths — pair-aggregated, so no (F, P, J) tensor
    cap = jnp.minimum(up[:, :, None, :],
                      jnp.swapaxes(down, 1, 2)[:, None, :, :])
    bh = (rate_pair[..., None] * pair * (cap <= _EPS)).sum()
    return load_up, load_down, through, qmean, bh


def _leaf_onehots(cfg: JxConfig, fb: FlowBatch
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(F, L) bfloat16 one-hots of every flow's source and destination
    leaf.  Leaves are static per flow, so they are built outside the
    scan; XLA's TPU compiler may instead fold them into each slot's
    contraction, where they cost a compare per element and no memory."""
    leaves = jnp.arange(cfg.n_leaves)
    return tuple((leaf[:, None] == leaves).astype(jnp.bfloat16)
                 for leaf in (fb.src_leaf, fb.dst_leaf))


def _exact_pieces(t: jnp.ndarray) -> List[jnp.ndarray]:
    """Pieces of a table whose sum, first to last, is `t` exactly.  A
    float32 table splits into three bfloat16 pieces, each the top 8
    significant bits of what the pieces before it left.  The bits are
    cut by a mask, not rounded, so neither a rounding mode nor a
    compiler's excess precision can change a piece; three pieces hold
    all 24 bits of a finite float32 whose nonzero magnitude is at least
    2**-100 (below that a piece would be subnormal, which the MXU
    flushes).  A float64 table (x64 parity runs, CPU) stays one piece."""
    if t.dtype != jnp.float32:
        return [t]
    pieces = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(t, jnp.uint32)
        head = jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)
        pieces.append(head.astype(jnp.bfloat16))
        t = t - head
    return pieces


def _leaf_sum(onehot: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """(F, N) per-flow values summed by the leaf of the (F, L) one-hot,
    (L, N): one matmul of the one-hot's transpose against the values'
    `_exact_pieces` side by side, the pieces' sums added first to last.
    Each product is exact (0 or 1 times a bfloat16 piece), so a bucket
    is a float32 sum of float32 terms, as a scatter-add's is, in another
    order: the same where the sums are exact, within rounding elsewhere.
    Terms below 2**-100 flush (`_exact_pieces`), and the values must be
    finite (0 × inf is NaN): the engine's are rates, pad flows' +0.0."""
    pieces = _exact_pieces(vals)
    sums = jnp.dot(onehot.T, jnp.concatenate(pieces, 1),
                   preferred_element_type=vals.dtype)
    sums = sums.reshape(sums.shape[0], len(pieces), -1)
    out = sums[:, 0]
    for i in range(1, len(pieces)):
        out = out + sums[:, i]
    return out


def _onehot_lookup(onehot: jnp.ndarray, tabs: jnp.ndarray,
                   assign: jnp.ndarray) -> jnp.ndarray:
    """`tabs[p, k, leaf[f], assign[f, p]]` as (K, F, P), from the (F, L)
    one-hot of each flow's leaf and leaf-major (P, K, L, S) tables.  One
    matmul of the one-hot against the tables' `_exact_pieces` side by
    side gives every flow its leaf's rows, (F, pieces·P·K·S); a
    select-and-sum over S picks the flow's spine, and the pieces add up
    in order.  A one-hot row has a single 1, so every product but one is
    an exact zero and each sum has one nonzero term: the result equals
    the point gather bit for bit, but for a -0.0 entry, which reads
    +0.0.  That needs finite tables (0 × inf is NaN): the engine's are
    bottleneck fractions in [0, 1] (`bottleneck_ref`), queues in [0,
    q_cap] (`queue_update_ref`) and link capacities."""
    P, K, L, S = tabs.shape
    pieces = _exact_pieces(tabs.transpose(2, 0, 1, 3).reshape(L, -1))
    rows = jnp.dot(onehot, jnp.concatenate(pieces, 1),
                   preferred_element_type=tabs.dtype)
    rows = rows.reshape(-1, len(pieces), P, K, S)
    pick = assign[:, None, :, None, None] == jnp.arange(S)
    picked = jnp.where(pick, rows, 0.0).sum(-1)          # (F, n, P, K)
    out = picked[:, 0]
    for i in range(1, len(pieces)):
        out = out + picked[:, i]
    return jnp.moveaxis(out, -1, 0)


def _link_values(fb: FlowBatch, assign: jnp.ndarray,
                 ups: Tuple[jnp.ndarray, ...],
                 downs: Tuple[jnp.ndarray, ...],
                 onehots: Optional[Tuple[jnp.ndarray, jnp.ndarray]]
                 ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Each link table's value on every flow's ECMP path, (F, P) each:
    `t[p, src_leaf, assign[:, p]]` of the (P, L, S) uplink tables `ups`,
    `t[p, assign[:, p], dst_leaf]` of the (P, S, L) downlink tables
    `downs`.  Point gathers, or with the `_leaf_onehots` the exact
    contraction of `_onehot_lookup` (see `ecmp_onehot`)."""
    if onehots is None:
        F, P = assign.shape
        p_iota = jnp.arange(P)[None, :].repeat(F, 0)
        return ([t[p_iota, fb.src_leaf[:, None], assign] for t in ups],
                [t[p_iota, assign, fb.dst_leaf[:, None]] for t in downs])
    src, dst = onehots
    return (list(_onehot_lookup(src, jnp.stack(ups, 1), assign)),
            list(_onehot_lookup(dst, jnp.stack(downs, 1).swapaxes(2, 3),
                                assign)))


def _ecmp_link_loads(cfg: JxConfig, fb: FlowBatch, assign: jnp.ndarray,
                     rate: jnp.ndarray,
                     onehots: Optional[Tuple[jnp.ndarray, jnp.ndarray]]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse ECMP link loads, (P, L, S) up and (P, S, L) down, of the
    (F, P) `rate` on each flow's `assign`ed spines: (plane, link)-keyed
    `segment_load`s, or where `onehot_agg` holds, `_leaf_sum`s of each
    flow's rate in the column of its (plane, spine) by its source and
    by its destination leaf (`onehots`)."""
    P, L, S = cfg.n_planes, cfg.n_leaves, cfg.n_spines
    F = assign.shape[0]
    if onehots is not None and onehot_agg(cfg, F):
        spine = assign[:, :, None] == jnp.arange(S)       # (F, P, S)
        v = jnp.where(spine, rate[:, :, None], 0.0).reshape(F, P * S)
        up, down = (_leaf_sum(oh, v).reshape(L, P, S) for oh in onehots)
        return up.transpose(1, 0, 2), down.transpose(1, 2, 0)
    pk = jnp.arange(P)[None, :]
    k_up = pk * (L * S) + fb.src_leaf[:, None] * S + assign
    k_dn = pk * (S * L) + assign * L + fb.dst_leaf[:, None]
    return (segment_load(rate, k_up, P * L * S).reshape(P, L, S),
            segment_load(rate, k_dn, P * S * L).reshape(P, S, L))


def _route_ecmp(cfg: JxConfig, carry: SimCarry, fabric_rate: jnp.ndarray,
                up: jnp.ndarray, down: jnp.ndarray, fb: FlowBatch,
                assign_segments: jnp.ndarray, load_fn: Callable,
                seg: jnp.ndarray,
                onehots: Optional[Tuple[jnp.ndarray, jnp.ndarray]]):
    """ECMP: one-hot spine choice from the precomputed assignment
    segment, loads via padded bucket sums.  `load_fn(seg)` yields the
    (P, LS+SL, C) permutation plan for the current capacity segment —
    a slice of this element's `_AggPerms.ecmp_load` on the per-group
    path, a row of the batch-deduplicated plan table on the megabatch
    path.  `onehots` (`_leaf_onehots`, or None for point gathers)
    select how each flow reads its path's links (`_link_values`) and
    how the sparse loads add up (`_ecmp_link_loads`)."""
    P, L, S = cfg.n_planes, cfg.n_leaves, cfg.n_spines
    assign = assign_segments[seg]                         # (F, P)
    if cfg.agg_mode == "sparse":
        load_up, load_down = _ecmp_link_loads(cfg, fb, assign,
                                              fabric_rate, onehots)
        f_up, f_down = _bottleneck(cfg, up, down, load_up, load_down)
    else:
        padT = jnp.concatenate(
            [fabric_rate, jnp.zeros((1, P), fabric_rate.dtype)], 0).T
        pidx = jnp.arange(P)[:, None, None]
        g = padT[pidx, load_fn(seg)]                      # (P, LS+SL, C)
        cap = jnp.concatenate(
            [up.reshape(P, L * S), down.reshape(P, S * L)], 1)
        loads, fracs = bucket_load_bottleneck(
            g, cap, eps=_EPS, use_pallas=cfg.use_pallas)
        load_up = loads[:, :L * S].reshape(P, L, S)
        load_down = loads[:, L * S:].reshape(P, S, L)
        f_up = fracs[:, :L * S].reshape(P, L, S)
        f_down = fracs[:, L * S:].reshape(P, S, L)
    # the physical capacities are looked up only under reaction
    n = 3 if cfg.react else 2
    (fu, qu, *cu), (fd, qd, *cd) = _link_values(
        fb, assign, (f_up, carry.q_up, up)[:n],
        (f_down, carry.q_down, down)[:n], onehots)
    through = fabric_rate * jnp.minimum(fu, fd)
    qmean = qu + qd
    if not cfg.react:
        return load_up, load_down, through, qmean
    # blackholed bytes: the one-hot assignment (already steered by the
    # lagged view on the host) landing on a physically dead path
    capF = jnp.minimum(cu[0], cd[0])
    bh = (fabric_rate * (capF <= _EPS)).sum()
    return load_up, load_down, through, qmean, bh


def _ft_maps(cfg: JxConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static fat-tree index maps: path→serving-agg and leaf→pod."""
    aj = jnp.arange(cfg.n_paths) // cfg.cores_per_agg
    pol = jnp.arange(cfg.n_leaves) // cfg.leaves_per_pod
    return aj, pol


def _route_pair_ft(cfg: JxConfig, carry: SimCarry,
                   fabric_rate: jnp.ndarray, up: jnp.ndarray,
                   down: jnp.ndarray, up2: jnp.ndarray,
                   down2: jnp.ndarray, upv: jnp.ndarray,
                   downv: jnp.ndarray, up2v: jnp.ndarray,
                   down2v: jnp.ndarray, aggs: _AggPerms,
                   pair_idx: jnp.ndarray, use_war):
    """Fat-tree AR / weighted-AR: the pair split runs over the path
    (= core) axis; capacity/queue per path compose stage A (leaf↔agg,
    via the path→agg map) with stage B (pod↔core) for cross-pod pairs.
    Mirrors `FluidFabric._pair_fractions_fat_tree` + `_step_fat_tree`
    operation for operation; the `*v` operands are the routing-visible
    (reaction-lagged) capacities — JSQ scores, weights, and remote
    weights come from them while delivery stays physical."""
    P, L, A = cfg.n_planes, cfg.n_leaves, cfg.n_aggs
    J, cpa = cfg.n_paths, cfg.cores_per_agg
    pods, lpp = cfg.n_pods, cfg.leaves_per_pod
    aj, pol = _ft_maps(cfg)
    cross = (pol[:, None] != pol[None, :])[None, :, :, None]
    upJ = upv[:, :, aj]                                   # (P, L, J)
    dnJ = downv[:, aj, :]                                 # (P, J, L)
    capA = jnp.minimum(upJ[:, :, None, :],
                       dnJ.transpose(0, 2, 1)[:, None, :, :])
    up2L = up2v[:, pol, :]                                # (P, L, J)
    dn2L = down2v[:, pol, :]
    capB = jnp.minimum(up2L[:, :, None, :], dn2L[:, None, :, :])
    cap = jnp.where(cross, jnp.minimum(capA, capB), capA)
    qA = (carry.q_up[:, :, aj][:, :, None, :] +
          carry.q_down[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
    qB = (carry.q2_up[:, pol, :][:, :, None, :] +
          carry.q2_down[:, pol, :][:, None, :, :])
    q = qA + jnp.where(cross, qB, 0.0)
    eff = jnp.minimum(dnJ, dn2L.transpose(0, 2, 1))       # (P, J, L)
    rw_arr = eff / jnp.maximum(eff.max(axis=1, keepdims=True), 1e-9)
    if isinstance(use_war, bool):
        rw = rw_arr if use_war else None
    else:
        rw = jnp.where(use_war, rw_arr, jnp.ones_like(rw_arr))
    w = cap if rw is None \
        else cap * rw.transpose(0, 2, 1)[:, None, :, :]
    pair = _k_pair_fractions(q, cap, w, nbins=cfg.jsq_bins,
                             temperature=cfg.ar_temperature, qmax=8.0,
                             use_pallas=cfg.use_pallas)
    rate_pair = _pair_rate_sum(cfg, fabric_rate, pair_idx, aggs)
    loadJ_up = jnp.einsum("plm,plmj->plj", rate_pair, pair)
    loadJ_dn = jnp.einsum("plm,plmj->pmj", rate_pair, pair)
    loadA_up = loadJ_up.reshape(P, L, A, cpa).sum(-1)     # (P, L, A)
    loadA_dn = loadJ_dn.reshape(P, L, A, cpa).sum(-1) \
        .transpose(0, 2, 1)                               # (P, A, L)
    ratex = rate_pair * (pol[:, None] != pol[None, :])[None]
    loadB_up = jnp.einsum("plm,plmj->plj", ratex, pair) \
        .reshape(P, pods, lpp, J).sum(2)                  # (P, pods, J)
    loadB_dn = jnp.einsum("plm,plmj->pmj", ratex, pair) \
        .reshape(P, pods, lpp, J).sum(2)
    fA_up, fA_dn = _bottleneck(cfg, up, down, loadA_up, loadA_dn)
    fB_up, fB_dn = _bottleneck(cfg, up2, down2, loadB_up, loadB_dn)
    sA = jnp.minimum(fA_up[:, :, aj][:, :, None, :],
                     fA_dn[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
    sB = jnp.minimum(fB_up[:, pol, :][:, :, None, :],
                     fB_dn[:, pol, :][:, None, :, :])
    scale_pair = jnp.where(cross, jnp.minimum(sA, sB), sA)
    path_scale = _path_sum(pair * scale_pair).reshape(P, L * L)
    through = fabric_rate * path_scale[:, pair_idx].T
    qmean = _path_sum(pair * q).reshape(P, L * L)[:, pair_idx].T
    if not cfg.react:
        return loadA_up, loadA_dn, loadB_up, loadB_dn, through, qmean
    # physical per-pair path capacity (the visible `cap` above steered
    # the split; a dead *physical* path blackholes what landed on it)
    capA_p = jnp.minimum(
        up[:, :, aj][:, :, None, :],
        down[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
    capB_p = jnp.minimum(up2[:, pol, :][:, :, None, :],
                         down2[:, pol, :][:, None, :, :])
    cap_p = jnp.where(cross, jnp.minimum(capA_p, capB_p), capA_p)
    bh = (rate_pair[..., None] * pair * (cap_p <= _EPS)).sum()
    return loadA_up, loadA_dn, loadB_up, loadB_dn, through, qmean, bh


def _route_ecmp_ft(cfg: JxConfig, carry: SimCarry,
                   fabric_rate: jnp.ndarray, up: jnp.ndarray,
                   down: jnp.ndarray, up2: jnp.ndarray,
                   down2: jnp.ndarray, fb: FlowBatch,
                   assign_segments: jnp.ndarray, load_fn: Callable,
                   seg: jnp.ndarray):
    """Fat-tree ECMP: the hash picks a path (= core) index; the serving
    agg follows from the canonical wiring.  Load plans stack stage-A
    up/down buckets and stage-B up/down buckets (cross-pod flows only)
    into one permutation matrix — see `_ecmp_load_plan`."""
    P, L, A = cfg.n_planes, cfg.n_leaves, cfg.n_aggs
    J, cpa = cfg.n_paths, cfg.cores_per_agg
    pods, lpp = cfg.n_pods, cfg.leaves_per_pod
    assign = assign_segments[seg]                         # (F, P)
    a_of = assign // cpa
    pod_s = fb.src_leaf // lpp
    pod_d = fb.dst_leaf // lpp
    cross = (pod_s != pod_d)[:, None]                     # (F, 1)
    p_iota = jnp.arange(P)[None, :].repeat(fabric_rate.shape[0], 0)
    if cfg.agg_mode == "sparse":
        pk = jnp.arange(P)[None, :]
        kAu = pk * (L * A) + fb.src_leaf[:, None] * A + a_of
        kAd = pk * (A * L) + a_of * L + fb.dst_leaf[:, None]
        kBu = pk * (pods * J) + pod_s[:, None] * J + assign
        kBd = pk * (pods * J) + pod_d[:, None] * J + assign
        # intra-pod flows add exact 0.0 to the stage-B buckets — the
        # NumPy engine does the same, so this is bit-equivalent to the
        # dense plan's masked exclusion
        vB = jnp.where(cross, fabric_rate, 0.0)
        loadA_up = segment_load(fabric_rate, kAu,
                                P * L * A).reshape(P, L, A)
        loadA_dn = segment_load(fabric_rate, kAd,
                                P * A * L).reshape(P, A, L)
        loadB_up = segment_load(vB, kBu,
                                P * pods * J).reshape(P, pods, J)
        loadB_dn = segment_load(vB, kBd,
                                P * pods * J).reshape(P, pods, J)
        fA_up, fA_dn = _bottleneck(cfg, up, down, loadA_up, loadA_dn)
        fB_up, fB_dn = _bottleneck(cfg, up2, down2, loadB_up, loadB_dn)
    else:
        padT = jnp.concatenate(
            [fabric_rate, jnp.zeros((1, P), fabric_rate.dtype)], 0).T
        pidx = jnp.arange(P)[:, None, None]
        g = padT[pidx, load_fn(seg)]        # (P, LA+AL+2*pods*J, C)
        o1, o2 = L * A, L * A + A * L
        o3 = o2 + pods * J
        cap = jnp.concatenate(
            [up.reshape(P, o1), down.reshape(P, o2 - o1),
             up2.reshape(P, pods * J), down2.reshape(P, pods * J)], 1)
        loads, fracs = bucket_load_bottleneck(
            g, cap, eps=_EPS, use_pallas=cfg.use_pallas)
        loadA_up = loads[:, :o1].reshape(P, L, A)
        loadA_dn = loads[:, o1:o2].reshape(P, A, L)
        loadB_up = loads[:, o2:o3].reshape(P, pods, J)
        loadB_dn = loads[:, o3:].reshape(P, pods, J)
        fA_up = fracs[:, :o1].reshape(P, L, A)
        fA_dn = fracs[:, o1:o2].reshape(P, A, L)
        fB_up = fracs[:, o2:o3].reshape(P, pods, J)
        fB_dn = fracs[:, o3:].reshape(P, pods, J)
    sA = jnp.minimum(fA_up[p_iota, fb.src_leaf[:, None], a_of],
                     fA_dn[p_iota, a_of, fb.dst_leaf[:, None]])
    sB = jnp.minimum(fB_up[p_iota, pod_s[:, None], assign],
                     fB_dn[p_iota, pod_d[:, None], assign])
    scale_f = jnp.where(cross, jnp.minimum(sA, sB), sA)
    through = fabric_rate * scale_f
    qA = (carry.q_up[p_iota, fb.src_leaf[:, None], a_of] +
          carry.q_down[p_iota, a_of, fb.dst_leaf[:, None]])
    qB = (carry.q2_up[p_iota, pod_s[:, None], assign] +
          carry.q2_down[p_iota, pod_d[:, None], assign])
    qmean = qA + jnp.where(cross, qB, 0.0)
    if not cfg.react:
        return loadA_up, loadA_dn, loadB_up, loadB_dn, through, qmean
    capAf = jnp.minimum(up[p_iota, fb.src_leaf[:, None], a_of],
                        down[p_iota, a_of, fb.dst_leaf[:, None]])
    capBf = jnp.minimum(up2[p_iota, pod_s[:, None], assign],
                        down2[p_iota, pod_d[:, None], assign])
    capF = jnp.where(cross, jnp.minimum(capAf, capBf), capAf)
    bh = (fabric_rate * (capF <= _EPS)).sum()
    return loadA_up, loadA_dn, loadB_up, loadB_dn, through, qmean, bh


def _slot_step(cfg: JxConfig, fb: FlowBatch, pair_idx: jnp.ndarray,
               onehots: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
               aggs: _AggPerms, assign_segments: jnp.ndarray,
               seg_up: jnp.ndarray, seg_down: jnp.ndarray,
               seg_acc: jnp.ndarray, seg_up2: jnp.ndarray,
               seg_down2: jnp.ndarray, seg_dem: jnp.ndarray,
               seg_vup: jnp.ndarray, seg_vdown: jnp.ndarray,
               seg_vup2: jnp.ndarray, seg_vdown2: jnp.ndarray,
               stack: Optional[StackIdx],
               load_fn: Callable, carry: SimCarry, xs):
    # timelines are piecewise-constant, so the scan carries only the
    # (n_seg, ...) boundary snapshots and gathers the current segment
    with jax.named_scope("slot/segment"):
        t, seg = xs
        up = seg_up[seg] * cfg.uplink_cap                     # (P, L, S|A)
        down = seg_down[seg] * cfg.uplink_cap                 # (P, S|A, L)
        acc = (seg_acc[seg] * cfg.access_cap).T               # (H, P)
        up2 = seg_up2[seg] * cfg.core_cap                     # (P, pods, C)
        down2 = seg_down2[seg] * cfg.core_cap
        if cfg.react:
            # routing-visible (detection-lagged) fabric view; access never
            # lags (NIC probes see host faults directly)
            upv = seg_vup[seg] * cfg.uplink_cap
            downv = seg_vdown[seg] * cfg.uplink_cap
            up2v = seg_vup2[seg] * cfg.core_cap
            down2v = seg_vdown2[seg] * cfg.core_cap
        else:
            # dead operands: routing sees physical truth, the traced
            # program is identical to the pre-reaction engine
            upv, downv, up2v, down2v = up, down, up2, down2

    with jax.named_scope("slot/plane_split"):
        demand = jnp.where(carry.done | (t < fb.start_slot), 0.0, fb.demand)
        if cfg.n_phases:
            # schedule workloads: piecewise-constant per-phase demand
            # multipliers, gathered per segment exactly like the capacity
            # snapshots above (lane 0 is the always-1.0 lane)
            demand = demand * seg_dem[seg][fb.phase]
        offered = _plane_split(cfg, carry.nic, demand, stack)  # (F, P)
        fabric_rate = jnp.where(fb.same_leaf[:, None], 0.0, offered)

    # ---- link loads + per-flow fabric throughput/queue, without any
    # (F, P, J) load intermediate: AR/WAR fractions are leaf-pair
    # quantities, so flows aggregate to (P, L, L) before touching the
    # path axis; ECMP's one-hot path choice reduces to (F, P) link
    # lookups (point gathers, or one-hot contractions on a TPU) + padded
    # bucket sums.  The topology kind is static, so the branch
    # list holds that kind's pair/ecmp implementations (fat-tree ones
    # also return stage-B loads); under traced dispatch `lax.switch`
    # evaluates both branches for the whole batch and selects per
    # element.
    with jax.named_scope("slot/route"):
        use_war = cfg.routing == "war" if stack is None else stack.is_war
        if cfg.kind == "fat_tree":
            branches = [
                partial(_route_pair_ft, cfg, carry, fabric_rate, up, down,
                        up2, down2, upv, downv, up2v, down2v, aggs,
                        pair_idx, use_war),
                partial(_route_ecmp_ft, cfg, carry, fabric_rate, up, down,
                        up2, down2, fb, assign_segments, load_fn, seg)]
        else:
            branches = [
                partial(_route_pair, cfg, carry, fabric_rate, up, down,
                        upv, downv, aggs, pair_idx, use_war),
                partial(_route_ecmp, cfg, carry, fabric_rate, up, down,
                        fb, assign_segments, load_fn, seg, onehots)]
        if stack is None:
            routed = branches[1 if cfg.routing == "ecmp" else 0]()
        elif isinstance(stack.route, int):
            # lane-sorted megabatch: the dispatcher grouped elements by
            # route, so the per-element index is concrete within the
            # lane and only that branch is traced (no switch tax)
            routed = branches[stack.route]()
        else:
            routed = jax.lax.switch(stack.route, branches)
        bh = routed[-1] if cfg.react else None
        routed = routed[:-1] if cfg.react else routed
        if cfg.kind == "fat_tree":
            load_up, load_down, loadB_up, loadB_dn, through, qmean = routed
        else:
            load_up, load_down, through, qmean = routed

    with jax.named_scope("slot/host_load"):
        src_oh, dst_oh = (onehots if onehot_agg(cfg, fb.src.shape[0])
                          else (None, None))
        load_acc_tx = _host_sum(cfg, offered, fb.src, aggs.src,
                                src_oh)                       # (H, P)
        load_acc_rx = _host_sum(cfg, offered, fb.dst, aggs.dst, dst_oh)

    # ---- bottleneck scaling (access; fabric scaling lives in the
    # routing branches) ----
    with jax.named_scope("slot/access_scale"):
        f_acc_tx = _k_bottleneck(acc, load_acc_tx, eps=_EPS,
                                 use_pallas=cfg.use_pallas)
        f_acc_rx = _k_bottleneck(acc, load_acc_rx, eps=_EPS,
                                 use_pallas=cfg.use_pallas)
        up_alive_tx = acc[fb.src] > _EPS                      # (F, P)
        up_alive_rx = acc[fb.dst] > _EPS

        local = jnp.where(fb.same_leaf[:, None], offered, 0.0)
        acc_scale = jnp.minimum(f_acc_tx[fb.src], f_acc_rx[fb.dst])
        achieved_pp = (through + local) * acc_scale
        achieved_pp = jnp.where(up_alive_tx & up_alive_rx, achieved_pp, 0.0)
        qmean = jnp.where(fb.same_leaf[:, None], 0.0, qmean)

    # ---- queue evolution (stage B only exists on fat_tree; the kind
    # is static, so leaf_spine programs carry the placeholders through
    # untouched) ----
    with jax.named_scope("slot/queue"):
        q_up, util = _k_queue_update(carry.q_up, load_up, up,
                                     q_cap=cfg.q_cap, eps=_EPS,
                                     use_pallas=cfg.use_pallas)
        q_down, _ = _k_queue_update(carry.q_down, load_down, down,
                                    q_cap=cfg.q_cap, eps=_EPS,
                                    use_pallas=cfg.use_pallas)
        if cfg.kind == "fat_tree":
            q2_up, _ = _k_queue_update(carry.q2_up, loadB_up, up2,
                                       q_cap=cfg.q_cap, eps=_EPS,
                                       use_pallas=cfg.use_pallas)
            q2_down, _ = _k_queue_update(carry.q2_down, loadB_dn, down2,
                                         q_cap=cfg.q_cap, eps=_EPS,
                                         use_pallas=cfg.use_pallas)
        else:
            q2_up, q2_down = carry.q2_up, carry.q2_down

    # ---- NIC control update (pre-stall rates, as in run_sim; rtt/ecn
    # derive from qmean inside the fused kernel) ----
    with jax.named_scope("slot/nic"):
        probe_ok = (acc[fb.src] > _EPS) & (acc[fb.dst] > _EPS)
        nic, rtt, ecn = _nic_update(cfg, carry.nic, qmean, probe_ok, t,
                                    stack)

    # ---- packet-loss stall + completion ----
    with jax.named_scope("slot/complete"):
        stalled = ((offered > 1e-9) & (achieved_pp <= 1e-9)).any(1)
        achieved = jnp.where(stalled, 0.0, achieved_pp.sum(1))

        remaining = carry.remaining - achieved
        newly = (~carry.done) & (remaining <= 0)
        w = jnp.maximum(offered, _EPS)
        qdelay = (((rtt * w).sum(1) / w.sum(1)) - cfg.base_rtt_us) \
            / cfg.slot_us
        completion = jnp.where(
            newly, t + jnp.ceil(qdelay).astype(carry.completion.dtype),
            carry.completion)
        done = carry.done | newly

        # ---- post-warmup accumulation (replaces dense (T, F) recording) ----
        r = cfg.record_every
        n_rec = (cfg.slots + r - 1) // r
        w0 = int(n_rec * cfg.warmup_frac)
        rec = (t % r) == 0
        if n_rec > w0:
            counted = rec & ((t // r) >= w0)
        else:
            counted = rec
        goodput_sum = carry.goodput_sum + jnp.where(counted, achieved, 0.0)
        total = achieved.sum()

    new_carry = SimCarry(
        q_up=q_up, q_down=q_down, q2_up=q2_up, q2_down=q2_down,
        nic=nic, remaining=remaining, done=done, completion=completion,
        goodput_sum=goodput_sum, util_up=util)
    extras = (bh,) if cfg.react else ()
    if not cfg.trace.enabled:
        if not cfg.react:
            return new_carry, total
        return new_carry, (total,) + extras
    # Trace outputs ride the scan's stacked ys (never the donated
    # carry); decimation happens in `_simulate`.  Padded flows offer
    # zero, so their host_bw contribution is exactly zero and the
    # megabatch finalizer only strips the flow-axis fields.
    sig = {
        "host_bw": lambda: _host_sum(
            cfg, jnp.where(stalled[:, None], 0.0, achieved_pp), fb.src,
            aggs.src, src_oh),
        "util": lambda: util,
        "queue": lambda: q_up,
        "ecn": lambda: ecn,
        "eligible": lambda: nic.eligible,
    }
    return new_carry, ((total,) + extras +
                       tuple(sig[f]() for f in cfg.trace.active_fields()))


def _simulate(cfg: JxConfig, fb: FlowBatch, seg_up, seg_down, seg_acc,
              seg_up2, seg_down2, seg_dem, seg_vup, seg_vdown, seg_vup2,
              seg_vdown2, assign_segments, aggs, seg_id,
              stack=None, carry0=None, ecmp_table=None, uid=None):
    if cfg.flow_chunk:
        # streaming path: the flow axis runs through the slot step in
        # fixed-size chunks (sparse aggregation only — `aggs`/the ECMP
        # plan table are never gathered there)
        from . import chunked
        return chunked.simulate_chunked(
            cfg, fb, seg_up, seg_down, seg_acc, seg_up2, seg_down2,
            seg_dem, seg_vup, seg_vdown, seg_vup2, seg_vdown2,
            assign_segments, seg_id, stack=stack, carry0=carry0)
    if carry0 is None:
        carry0 = init_carry(fb, cfg)
    if ecmp_table is None:
        def load_fn(seg):
            return aggs.ecmp_load[seg]
    else:
        # batch-deduplicated plan table: `uid` picks this element's row
        def load_fn(seg):
            return ecmp_table[uid, seg]
    pair_idx = fb.src_leaf * cfg.n_leaves + fb.dst_leaf
    onehots = (_leaf_onehots(cfg, fb)
               if ecmp_onehot(cfg, fb.src.shape[0]) else None)
    xs = (jnp.arange(cfg.slots), seg_id)
    step = partial(_slot_step, cfg, fb, pair_idx, onehots, aggs,
                   assign_segments, jnp.asarray(seg_up),
                   jnp.asarray(seg_down), jnp.asarray(seg_acc),
                   jnp.asarray(seg_up2), jnp.asarray(seg_down2),
                   jnp.asarray(seg_dem), jnp.asarray(seg_vup),
                   jnp.asarray(seg_vdown), jnp.asarray(seg_vup2),
                   jnp.asarray(seg_vdown2), stack, load_fn)
    carry, ys = jax.lax.scan(step, carry0, xs)
    # ys layout: raw scalar (no trace, no react) | tuple of
    # (total, [blackhole], *trace-fields) — blackhole stays full-rate
    # (T,), trace fields decimate by trace.every
    bh = ()
    if cfg.trace.enabled or cfg.react:
        totals = ys[0]
        rest = ys[1:]
        if cfg.react:
            bh = (rest[0],)
            rest = rest[1:]
        tail = tuple(y[::cfg.trace.every] for y in rest)
    else:
        totals, tail = ys, ()
    r = cfg.record_every
    n_rec = (cfg.slots + r - 1) // r
    w0 = int(n_rec * cfg.warmup_frac)
    frames = (n_rec - w0) if n_rec > w0 else n_rec
    return (carry.goodput_sum / frames, carry.completion, totals,
            carry.util_up) + bh + tail


def _simulate_mb(cfg: JxConfig, stack: StackIdx, carry0: SimCarry,
                 fb: FlowBatch, seg_up, seg_down, seg_acc, seg_up2,
                 seg_down2, seg_dem, seg_vup, seg_vdown, seg_vup2,
                 seg_vdown2, assign_segments, aggs, uid, seg_id,
                 ecmp_table):
    """Megabatch element: traced branch dispatch + donated carry.  Every
    argument between `stack` and `seg_id` (inclusive) is vmapped;
    `ecmp_table` is batch-constant (the deduplicated ECMP plan table)."""
    return _simulate(cfg, fb, seg_up, seg_down, seg_acc, seg_up2,
                     seg_down2, seg_dem, seg_vup, seg_vdown, seg_vup2,
                     seg_vdown2, assign_segments, aggs, seg_id,
                     stack=stack, carry0=carry0, ecmp_table=ecmp_table,
                     uid=uid)


def _jitted(cfg: JxConfig, batched: bool, n_shards: int = 1):
    """Compiled per-group entry point, memoized on (cfg, batch form,
    shard count, *and the visible device set*) — a `pmap` callable built
    for N devices must not be silently reused if the device set changes
    mid-process (regression-tested)."""
    key = ("group", cfg, batched, n_shards, _device_fingerprint())
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    fn = partial(_simulate, cfg)
    if not batched:
        fn = jax.jit(fn)
    else:
        fn = jax.vmap(fn, in_axes=(0,) * 13 + (None,))
        if n_shards == 1:
            fn = jax.jit(fn)
        else:
            # shard the batch axis over host devices: XLA CPU serializes
            # separate executions even across devices, but one pmap
            # launch runs its per-device shards on parallel threads —
            # the single-process equivalent of the NumPy backend's
            # process pool
            fn = jax.pmap(fn, in_axes=(0,) * 13 + (None,))
    _JIT_CACHE[key] = fn
    return fn


def lane_mesh(n_shards: int) -> "jax.sharding.Mesh":
    """1-D device mesh over the megabatch lane (batch) axis.  Today the
    axis spans local host devices; under `jax.distributed` the same
    `Mesh(("lane",))` layout extends to multi-process global devices —
    `_jitted_mb`'s NamedSharding code path is written against the mesh,
    not the device list, so only this constructor changes."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n_shards]), ("lane",))


def _jitted_mb(cfg: JxConfig, n_shards: int = 1,
               lanes: Optional[Tuple[Tuple[int, int], ...]] = None):
    """Compiled megabatch entry point: one `jit(vmap)` covering every
    (routing, nic) via traced `StackIdx`, with the initial scan carry
    donated — the step rewrites it wholesale, so XLA reuses its buffers
    instead of allocating a second batch.  With `n_shards > 1` the
    batch axis is split over a 1-D "lane" device mesh (`lane_mesh`):
    operands arrive flat `(B, ...)` and `shard_map` hands each device
    its own contiguous block — the replacement for the old
    device-major `pmap` layout, structured to extend to
    `jax.distributed` meshes.

    `lanes` is the dispatcher's static per-shard layout: a tuple of
    `(route_index, n_elements)` runs.  Elements are lane-sorted by the
    dispatcher, so within a run the route index is concrete and only
    that routing branch is traced; `None` falls back to the fully
    per-element `lax.switch` (every branch evaluated batch-wide,
    selected per element) — semantically identical, slower."""
    key = ("mega", cfg, n_shards, lanes, _device_fingerprint())
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    if lanes is None:
        body = jax.vmap(partial(_simulate_mb, cfg),
                        in_axes=(0,) * 17 + (None,))
    else:
        stack_axes = StackIdx(route=None, is_war=0, nic=0, is_esr=0)
        v = jax.vmap(partial(_simulate_mb, cfg),
                     in_axes=(stack_axes,) + (0,) * 16 + (None,))
        tm = jax.tree_util.tree_map

        def body(stack, carry0, fb, up, down, acc, up2, down2, dem,
                 vup, vdown, vup2, vdown2, assign, aggs, uid, seg_id,
                 table):
            outs, off = [], 0
            for route, n in lanes:
                def cut(x, off=off, n=n):
                    return jax.lax.slice_in_dim(x, off, off + n, axis=0)
                st = tm(cut, stack)._replace(route=route)
                outs.append(v(st, tm(cut, carry0), tm(cut, fb), cut(up),
                              cut(down), cut(acc), cut(up2), cut(down2),
                              cut(dem), cut(vup), cut(vdown), cut(vup2),
                              cut(vdown2), cut(assign), tm(cut, aggs),
                              cut(uid), cut(seg_id), table))
                off += n
            return tuple(jnp.concatenate(parts, 0)
                         for parts in zip(*outs))

    if n_shards == 1:
        fn = jax.jit(body, donate_argnums=(1,))
    else:
        from jax.sharding import PartitionSpec

        # flat (B, ...) operands split evenly over the "lane" axis; each
        # device runs the lanes body (whose run lengths are per-shard)
        # on its own block.  `shard_map` rather than GSPMD partitioning:
        # Mosaic kernels cannot be partitioned automatically.  The body
        # has no collectives, so nothing needs the varying-axes check
        # (which Pallas output shapes and scan carries do not carry).
        lane = PartitionSpec("lane")
        fn = jax.jit(
            jax.shard_map(body, mesh=lane_mesh(n_shards),
                          in_specs=(lane,) * 17 + (PartitionSpec(),),
                          out_specs=lane, check_vma=False),
            donate_argnums=(1,))
    _JIT_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

_F32_LOCK = threading.Lock()
_F32_WARNED: set = set()
_F32_OVERFLOWS: List[Dict] = []


def strict_f32() -> bool:
    """`REPRO_JX_STRICT_F32=1` turns the float32 bytes_total overflow
    warning into a hard error."""
    return bool(_env_flag("REPRO_JX_STRICT_F32"))


def f32_overflow_log() -> Tuple[Dict, ...]:
    """Every float32 bytes_total overflow condition seen this process,
    in detection order — `{"spec": name, "max_bytes": float}` each.
    Executors slice this by length to attach the overflows of one run
    to its flight record."""
    with _F32_LOCK:
        return tuple(dict(d) for d in _F32_OVERFLOWS)


def _warn_f32_bytes(name: str, fa: FlowArrays, stacklevel: int = 3
                    ) -> None:
    if jax.config.jax_enable_x64:
        return
    finite = fa.bytes_total[np.isfinite(fa.bytes_total)]
    if not (finite.size and finite.max() > 2 ** 24):
        return
    msg = (f"{name}: bytes_total up to {finite.max():.3g} "
           "exceeds float32 integer resolution (2^24); remaining-"
           "bytes tracking will stall and transfers may never "
           "complete — enable x64 (JAX_ENABLE_X64=1) or rescale "
           "bytes_total")
    with _F32_LOCK:
        _F32_OVERFLOWS.append(
            {"spec": name, "max_bytes": float(finite.max())})
        first = name not in _F32_WARNED
        _F32_WARNED.add(name)
    if strict_f32():
        raise ValueError(msg)
    if first:
        # stdlib warnings dedup by (message, category, module, lineno) —
        # i.e. by *call site* — so a second spec tripping the same
        # condition would be silently swallowed under the default
        # filter.  Dedup per spec name ourselves and always register
        # the condition in `f32_overflow_log` above.
        import warnings
        warnings.warn(msg, stacklevel=stacklevel)


def _prepared(compiled
              ) -> Tuple[JxConfig, FlowArrays, FaultTimeline,
                         Optional[np.ndarray],
                         Optional[FaultTimeline]]:
    """Returns `(cfg, flow arrays, physical timeline, phase mult,
    visible timeline)` — the visible timeline is the reaction-lagged
    view (None when reaction is off, or the physical timeline itself
    when the reaction's total lag is zero)."""
    from repro.scenarios.spec import reaction_lag
    spec = compiled.spec
    cfg = JxConfig.from_sim(compiled.cfg, spec.topo)
    fa = FlowArrays.build(compiled.flows, compiled.topo)
    _warn_f32_bytes(spec.name, fa, stacklevel=4)
    pm = getattr(compiled, "phase_mult", None)
    if pm is not None:
        cfg = replace(cfg, n_phases=int(pm.shape[1]))
    tl = compile_fault_timeline(spec)
    vtl = None
    r = spec.reaction
    if r is not None and r.enabled:
        cfg = replace(cfg, react=True)
        lag = reaction_lag(r, spec.sim.routing)
        vtl = lagged_timeline(tl, lag) if lag > 0 else tl
    chunk = flow_chunk_default(len(fa), cfg.n_planes, cfg.agg_mode)
    if chunk and not cfg.trace.enabled:
        # chunked streaming implies sparse aggregation (a forced
        # REPRO_JX_FLOW_CHUNK coerces it; the auto heuristic only fires
        # on already-sparse shapes)
        cfg = replace(cfg, agg_mode="sparse", flow_chunk=chunk)
    return cfg, fa, tl, pm, vtl


def phase_boundaries(pm: Optional[np.ndarray]) -> List[int]:
    """Slots where any phase-multiplier lane changes value ([0] always
    included) — unioned with the fault timeline's `change_slots()` so
    the piecewise-constant segment machinery covers both.  Phase changes
    never alter path capacity, so the ECMP re-hash replay draws no extra
    RNG at these boundaries and numpy↔jax parity is preserved."""
    if pm is None:
        return [0]
    diff = np.any(pm[1:] != pm[:-1], axis=1)
    return [0] + (np.flatnonzero(diff) + 1).tolist()


def _seg_dem(pm: Optional[np.ndarray], boundaries) -> np.ndarray:
    """(n_seg, K) demand-multiplier snapshots; a (n_seg, 1) ones
    placeholder when no schedule is present (cfg.n_phases == 0 compiles
    the gather away — the operand is dead)."""
    b = list(boundaries)
    if pm is None:
        return np.ones((len(b), 1))
    return np.asarray(pm)[b]


def _seg_id(boundaries, slots: int) -> np.ndarray:
    """(T,) index of the capacity segment governing each slot."""
    return (np.searchsorted(np.asarray(list(boundaries)),
                            np.arange(slots), side="right") - 1) \
        .astype(np.int32)


def _assign_for(cfg: JxConfig, fa: FlowArrays, tl: FaultTimeline,
                seed: int, boundaries,
                vtl: Optional[FaultTimeline] = None,
                mode: str = "instant",
                backup: Optional[np.ndarray] = None) -> np.ndarray:
    if cfg.routing == "ecmp":
        return ecmp_assign_segments(
            fa.src_leaf, fa.dst_leaf, tl, seed, cfg.n_paths, boundaries,
            uplink_cap=cfg.uplink_cap, core_cap=cfg.core_cap,
            cores_per_agg=cfg.cores_per_agg,
            leaves_per_pod=cfg.leaves_per_pod,
            vis_timeline=vtl, mode=mode, backup=backup)
    return np.zeros((1, len(fa), cfg.n_planes), np.int32)


def _seg_caps(tl: FaultTimeline, boundaries
              ) -> Tuple[np.ndarray, ...]:
    """Compress a dense timeline to its boundary snapshots
    ((n_seg, ...) each) — the engine re-expands via `_seg_id` gathers.
    Stage-B snapshots are (n_seg, P, 1, 1) ones on leaf_spine (passed
    through but never read by that kind's traced program)."""
    b = list(boundaries)
    if tl.up2 is not None:
        return (tl.up[b], tl.down[b], tl.access[b], tl.up2[b],
                tl.down2[b])
    P = tl.up.shape[1]
    dummy = np.ones((len(b), P, 1, 1))
    return tl.up[b], tl.down[b], tl.access[b], dummy, dummy


def _vis_seg_caps(vtl: Optional[FaultTimeline], boundaries,
                  n_planes: int) -> Tuple[np.ndarray, ...]:
    """The four routing-visible fabric snapshots (up, down, up2, down2);
    inert `(n_seg, P, 1, 1)` ones when reaction is off (`cfg.react=False`
    never reads them — the operands are dead)."""
    b = list(boundaries)
    if vtl is None:
        dummy = np.ones((len(b), n_planes, 1, 1))
        return dummy, dummy, dummy, dummy
    if vtl.up2 is not None:
        return vtl.up[b], vtl.down[b], vtl.up2[b], vtl.down2[b]
    dummy = np.ones((len(b), n_planes, 1, 1))
    return vtl.up[b], vtl.down[b], dummy, dummy


def _masked_perm_matrix(keys: np.ndarray, mask: np.ndarray,
                        n_buckets: int, width: int,
                        pad: int) -> np.ndarray:
    """`_perm_matrix` over only the flows where `mask` — the stage-B
    fat-tree plans exclude intra-pod flows (which never touch a core
    link; the NumPy path adds exact 0.0 for them, so exclusion is
    bit-equivalent).  Flow order is preserved within buckets."""
    perm = np.full((n_buckets, width), pad, np.int32)
    idx = np.flatnonzero(mask)
    sub = np.asarray(keys)[idx]
    order = np.argsort(sub, kind="stable")
    sk = sub[order]
    counts = np.bincount(sk, minlength=n_buckets)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    ranks = np.arange(len(sk)) - starts[sk]
    perm[sk, ranks] = idx[order]
    return perm


def _ft_ecmp_keys(cfg: JxConfig, fa: FlowArrays, assign_gp: np.ndarray
                  ) -> Tuple[Tuple[np.ndarray, np.ndarray, int], ...]:
    """The four fat-tree load-bucket key families for one (segment,
    plane) assignment column: (keys, mask, n_buckets) each, in plan
    row order (A-up, A-down, B-up, B-down)."""
    L, A = cfg.n_leaves, cfg.n_aggs
    J, pods = cfg.n_paths, cfg.n_pods
    a_of = assign_gp // cfg.cores_per_agg
    pod_s = fa.src_leaf // cfg.leaves_per_pod
    pod_d = fa.dst_leaf // cfg.leaves_per_pod
    cross = pod_s != pod_d
    every = np.ones(len(fa), bool)
    return ((fa.src_leaf * A + a_of, every, L * A),
            (a_of * L + fa.dst_leaf, every, A * L),
            (pod_s * J + assign_gp, cross, pods * J),
            (pod_d * J + assign_gp, cross, pods * J))


def _plan_rows(cfg: JxConfig) -> int:
    """Row count of one ECMP load plan: stage-A up+down buckets, plus
    the two stage-B bucket families on fat_tree."""
    if cfg.kind == "fat_tree":
        L, A = cfg.n_leaves, cfg.n_aggs
        return L * A + A * L + 2 * cfg.n_pods * cfg.n_paths
    return 2 * cfg.n_leaves * cfg.n_spines


def _agg_widths(cfg: JxConfig, fa: FlowArrays,
                assign: np.ndarray) -> Tuple[int, ...]:
    """Max bucket sizes for each aggregation axis (shared across a batch
    so the padded perm matrices stack)."""
    if cfg.agg_mode == "sparse":
        # sparse aggregation never materializes the gather plans, so
        # their widths are irrelevant (and the bincount sweep over every
        # (segment, plane) column would dominate prep time at scale)
        return (1, 1, 1, 1)

    def w(keys, n, mask=None):
        if mask is not None:
            keys = keys[mask]
            if keys.size == 0:
                return 1
        return max(1, int(np.bincount(keys, minlength=n).max()))
    H, L, S, P = cfg.n_hosts, cfg.n_leaves, cfg.n_spines, cfg.n_planes
    wu = 1
    if cfg.routing == "ecmp":
        for g in range(assign.shape[0]):
            for p in range(P):
                if cfg.kind == "fat_tree":
                    wu = max([wu] + [
                        w(keys, n, mask) for keys, mask, n in
                        _ft_ecmp_keys(cfg, fa, assign[g][:, p])])
                else:
                    wu = max(wu,
                             w(fa.src_leaf * S + assign[g][:, p], L * S),
                             w(assign[g][:, p] * L + fa.dst_leaf, S * L))
    return (w(fa.src, H), w(fa.dst, H),
            w(fa.src_leaf * L + fa.dst_leaf, L * L), wu)


def _ecmp_load_plan(cfg: JxConfig, fa: FlowArrays, assign: np.ndarray,
                    wu: int, pad: int) -> np.ndarray:
    """(n_seg, P, `_plan_rows(cfg)`, wu) ECMP load-aggregation plan (see
    `_AggPerms.ecmp_load`) — the single builder shared by the per-group
    and megabatch paths, so their 1e-5 row-identity cannot drift."""
    P, L, S = cfg.n_planes, cfg.n_leaves, cfg.n_spines

    def plane(g, p):
        if cfg.kind == "fat_tree":
            return np.concatenate([
                _masked_perm_matrix(keys, mask, n, wu, pad)
                for keys, mask, n in
                _ft_ecmp_keys(cfg, fa, assign[g][:, p])])
        return np.concatenate([
            _perm_matrix(fa.src_leaf * S + assign[g][:, p],
                         L * S, wu, pad),
            _perm_matrix(assign[g][:, p] * L + fa.dst_leaf,
                         S * L, wu, pad)])

    return np.stack([
        np.stack([plane(g, p) for p in range(P)])
        for g in range(assign.shape[0])])


def _aggs_for(cfg: JxConfig, fa: FlowArrays, assign: np.ndarray,
              widths: Tuple[int, ...],
              pad: Optional[int] = None) -> _AggPerms:
    """`pad` is the index that reads the appended zero row in
    `_seg_sum` — the row count of the (possibly flow-padded) batch, not
    necessarily `len(fa)`."""
    ws, wd, wp, wu = widths
    H, L, P = cfg.n_hosts, cfg.n_leaves, cfg.n_planes
    F = len(fa) if pad is None else pad
    if cfg.agg_mode == "sparse":
        # sparse mode aggregates by (plane, link) keys computed from the
        # flow batch inside the traced program; the gather plans are
        # never indexed, so ship inert minimal placeholders
        z = np.zeros((1, 1), np.int32)
        return _AggPerms(src=z, dst=z, pair=z,
                         ecmp_load=np.zeros((1, P, 1, 1), np.int32))
    if cfg.routing == "ecmp":
        load = _ecmp_load_plan(cfg, fa, assign, wu, F)
    else:
        load = np.full((1, P, 1, 1), F, np.int32)
    return _AggPerms(
        src=_perm_matrix(fa.src, H, ws, F),
        dst=_perm_matrix(fa.dst, H, wd, F),
        pair=_perm_matrix(fa.src_leaf * L + fa.dst_leaf, L * L, wp, F),
        ecmp_load=load)


def _wrap(cfg: JxConfig, fa: FlowArrays, out) -> JxSimResult:
    mean_goodput, completion, totals, util = \
        (np.asarray(o) for o in out[:4])
    idx = 4
    bh = None
    if cfg.react:
        bh = np.asarray(out[idx])
        idx += 1
    trace = None
    if cfg.trace.enabled:
        trace = {"slot": cfg.trace.recorded_slots(cfg.slots)}
        trace.update((name, np.asarray(arr)) for name, arr
                     in zip(cfg.trace.active_fields(), out[idx:]))
    return JxSimResult(
        mean_goodput=mean_goodput,
        completion_slot=completion.astype(np.int64),
        total_goodput=totals[::cfg.record_every],
        util_up_last=util, groups=fa.groups, group_of=fa.group,
        slot_us=cfg.slot_us, trace=trace, blackhole_timeline=bh)


def run_compiled(compiled) -> JxSimResult:
    """Simulate one `CompiledScenario` on the JAX backend."""
    global _BACKEND_USED
    _BACKEND_USED = True
    cfg, fa, tl, pm, vtl = _prepared(compiled)
    boundaries = set(tl.change_slots()) | set(phase_boundaries(pm))
    if vtl is not None:
        boundaries |= set(vtl.change_slots())
    boundaries = tuple(sorted(boundaries))
    r = compiled.spec.reaction
    segs = _assign_for(cfg, fa, tl, compiled.cfg.seed, boundaries,
                       vtl=vtl, mode=r.mode if cfg.react else "instant",
                       backup=getattr(compiled, "backup", None))
    aggs = _aggs_for(cfg, fa, segs, _agg_widths(cfg, fa, segs))
    up, down, acc, up2, down2 = _seg_caps(tl, boundaries)
    vup, vdown, vup2, vdown2 = _vis_seg_caps(
        vtl if cfg.react else None, boundaries, cfg.n_planes)
    args = (FlowBatch.from_arrays(fa), up, down, acc, up2, down2,
            _seg_dem(pm, boundaries), vup, vdown, vup2, vdown2, segs,
            aggs, _seg_id(boundaries, cfg.slots))
    _record_launch("group", (cfg, False, 1), args)
    out = _jitted(cfg, False)(*args)
    return _wrap(cfg, fa, out)


def dispatch_compiled_batch(points: List):
    """Build and asynchronously dispatch one batch of structurally
    identical `CompiledScenario`s (same scenario / routing / nic /
    slots — only seeds differ).  Returns an opaque handle for
    `finalize_batch`; the computation runs concurrently with whatever
    the caller does next (JAX CPU execution is async).  With
    `XLA_FLAGS=--xla_force_host_platform_device_count=N` the batch axis
    is `pmap`-sharded over the N host devices (padding the batch by
    replicating the last point if needed), keeping every core busy
    without a process pool."""
    global _BACKEND_USED
    _BACKEND_USED = True
    prepared = [_prepared(c) for c in points]
    cfg = prepared[0][0]
    F = len(prepared[0][1])
    for c, (cfg_i, fa_i, _, _, _) in zip(points, prepared):
        if cfg_i != cfg or len(fa_i) != F:
            raise ValueError(
                "batched points must be structurally identical "
                f"(got {cfg_i} with {len(fa_i)} flows vs {cfg} with {F}); "
                "group grid points by (scenario, routing, nic) first")
    # shared segment boundaries: union of capacity-change AND
    # phase-change slots (and visible-capacity changes under reaction),
    # so every element's ECMP re-hash replay sees each capacity change
    # exactly once and the demand timeline is piecewise-constant per
    # segment
    boundaries = tuple(sorted(
        {b for _, _, tl, _, _ in prepared for b in tl.change_slots()}
        | {b for _, _, _, pm, _ in prepared
           for b in phase_boundaries(pm)}
        | {b for _, _, _, _, vtl in prepared if vtl is not None
           for b in vtl.change_slots()}))
    assigns = [
        _assign_for(
            cfg, fa, tl, c.cfg.seed, boundaries, vtl=vtl,
            mode=(c.spec.reaction.mode if cfg.react else "instant"),
            backup=getattr(c, "backup", None))
        for c, (_, fa, tl, _, vtl) in zip(points, prepared)]
    widths = tuple(map(max, zip(*(
        _agg_widths(cfg, fa, a)
        for (_, fa, _, _, _), a in zip(prepared, assigns)))))
    aggs = [_aggs_for(cfg, fa, a, widths)
            for (_, fa, _, _, _), a in zip(prepared, assigns)]
    fb = FlowBatch.stack([fa for _, fa, _, _, _ in prepared])
    caps = [_seg_caps(tl, boundaries) for _, _, tl, _, _ in prepared]
    up, down, acc, up2, down2 = (np.stack(col) for col in zip(*caps))
    vcaps = [_vis_seg_caps(vtl if cfg.react else None, boundaries,
                           cfg.n_planes)
             for _, _, _, _, vtl in prepared]
    vup, vdown, vup2, vdown2 = (np.stack(col) for col in zip(*vcaps))
    dem = np.stack([_seg_dem(pm, boundaries)
                    for _, _, _, pm, _ in prepared])
    seg_id = _seg_id(boundaries, cfg.slots)
    aggs_b = _AggPerms(*(np.stack(col) for col in zip(*aggs)))
    args = [fb, up, down, acc, up2, down2, dem, vup, vdown, vup2,
            vdown2, np.stack(assigns), aggs_b]
    B = len(points)
    n_dev = len(jax.devices())
    shards = min(B, n_dev) if n_dev > 1 and B > 1 else 1
    if shards > 1:
        padded = -B % shards

        def shape(a):
            if padded:
                a = np.concatenate(
                    [np.asarray(a),
                     np.repeat(np.asarray(a)[-1:], padded, 0)])
            return np.asarray(a).reshape(
                (shards, (B + padded) // shards) + np.shape(a)[1:])

        args = [jax.tree_util.tree_map(shape, a) for a in args]
    _record_launch("group", (cfg, True, shards), args)
    out = _jitted(cfg, True, shards)(*args, seg_id)
    # keep only what finalize needs — dropping the dense per-point
    # timelines here frees O(B*T*fabric) host memory while the batch
    # computes
    return cfg, [fa for _, fa, _, _, _ in prepared], shards, out


def finalize_batch(handle) -> List[JxSimResult]:
    """Block on a `dispatch_compiled_batch` handle and unpack per-point
    results (dropping any pmap padding)."""
    cfg, fas, shards, out = handle
    outs = [np.asarray(o) for o in out]
    if shards > 1:
        outs = [o.reshape((-1,) + o.shape[2:]) for o in outs]
    return [_wrap(cfg, fa, [o[b] for o in outs])
            for b, fa in enumerate(fas)]


def run_compiled_batch(points: List) -> List[JxSimResult]:
    """Simulate a batch of `CompiledScenario`s that share structure as
    one batched (vmap, pmap-sharded when multiple host devices exist)
    computation — the JAX replacement for the process-pool sweep."""
    return finalize_batch(dispatch_compiled_batch(points))
