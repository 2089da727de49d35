"""Megabatch dispatch: one XLA launch per experiment sweep.

The JAX backend's only dispatch path: it stacks *every* point of a grid
into one `jit(vmap)` launch — sharded over the lane axis with a
`jax.sharding` Mesh when multiple devices are visible — so a routing ×
nic × fault × seed grid pays one compile and one launch per structure,
not one per point (`engine.run_compiled` is a one-point megabatch):

  * `routing` / `nic` become per-element `StackIdx` branch selectors,
    resolved by `lax.switch` inside the traced program
    (`JxConfig.routing == nic == "*"`);
  * flow counts and fault-timeline segment counts are padded up to
    power-of-two buckets so heterogeneous points share static shapes —
    pad flows are inert (zero demand, infinite bytes, never started)
    and pad segments replicate the final capacity snapshot, which the
    per-slot segment-id gather never selects;
  * host-side prep is content-memoized: fault timelines, flow arrays,
    ECMP assignment replays, and aggregation plans are built once per
    distinct (faults, slots, workload-seed, …) key instead of once per
    grid point — a fault × seed grid shares almost everything;
  * the big ECMP permutation plans are deduplicated into one
    batch-constant table (`ecmp_table`) indexed by a per-element `uid`,
    instead of being replicated across the batch (for a 120-point grid
    this shrinks the transfer from O(B) plans to O(#distinct) plans);
  * the initial scan carry is built host-side and donated, so XLA
    reuses its buffers for the carry that the scan rewrites.

Points that cannot share a program (different topology shape, slot
count, record cadence, … or a different shape bucket) split into
multiple launches — still one per *structure*, never one per point.
Row-identity of a fused grid with each point run alone (1e-5, x64) is
pinned by `tests/test_megabatch.py`.

Multi-device runs hand the batch to `engine._jitted_mb` as flat
`(B, ...)` arrays, dealt device-major; `shard_map` gives each mesh device
its contiguous block, so every device sees the same static per-shard
lane layout.  The mesh is 1-D over `jax.devices()`, so the same code
path extends to multi-process `jax.distributed` meshes later.

`plan_megabatch` / `dispatch_planned` split the grouping (cheap,
structural) from the host prep + launch (expensive, memoized) so
`experiments/execute.py` can pipeline: prep bucket k+1 on a worker
thread while the device executes bucket k.  `dispatch_megabatch` is the
sequential composition of the two.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.netsim.fabric import FlowArrays
from repro.netsim.flight import count, span
from repro.trace import FLOW_AXIS_FIELDS

from repro.scenarios.spec import reaction_lag

from . import engine
from .engine import JxConfig, JxSimResult, StackIdx, stack_idx_for
from .events import compile_fault_timeline, lagged_timeline


def _bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= n (>= lo) — the static-shape buckets
    that let heterogeneous grid points share one compiled program."""
    return max(lo, 1 << max(0, int(n - 1).bit_length()))


# flow-count buckets start here: tiny scenarios all land in one shape
FLOW_BUCKET_MIN = 8


@dataclass
class _Point:
    """Host-side prep for one grid point.  The `*_key` fields are the
    content keys under which shared artifacts were memoized."""
    index: int
    cfg: JxConfig               # struct cfg (routing = nic = "*")
    routing: str
    nic: str
    fa_key: Tuple
    tl_key: Tuple
    assign_key: Optional[Tuple]
    fa: FlowArrays
    boundaries: Tuple[int, ...]
    caps: Tuple[np.ndarray, np.ndarray, np.ndarray]  # (n_seg, ...) each
    assign: Optional[np.ndarray]  # (n_seg, F, P), ECMP points only
    widths: Tuple[int, ...]
    dem: np.ndarray = None        # (n_seg, K) phase-demand snapshots
    # routing-visible capacity snapshots (4 arrays; inert ones-dummies
    # when the point's reaction is off)
    vcaps: Tuple[np.ndarray, ...] = ()


def _struct_cfg(compiled) -> JxConfig:
    """`JxConfig` with routing/nic lifted out of the static key.  The
    swlb reaction delay is resolved unconditionally (SimConfig returns 0
    for non-swlb NICs, but here swlb is one traced branch of every
    program and only swlb elements ever read it).  Schedule points set
    `n_phases` to the pow2 bucket of their lane count, so schedule and
    non-schedule points split into separate structural groups (each
    still one compile per bucket)."""
    sim = compiled.cfg
    base = JxConfig.from_sim(sim, compiled.spec.topo)
    delay = int(sim.sw_lb_delay_ms * 1000 / sim.slot_us)
    pm = getattr(compiled, "phase_mult", None)
    n_phases = _bucket(pm.shape[1]) if pm is not None else 0
    r = compiled.spec.reaction
    react = r is not None and r.enabled
    cfg = replace(base, routing="*", nic="*", sw_lb_delay_slots=delay,
                  n_phases=n_phases, react=react)
    # chunked flow streaming: size the chunk off the point's flow
    # *bucket* (not the raw count) so every point of a shape bucket
    # lands in the same structural group with the same chunk length
    chunk = engine.flow_chunk_default(
        _bucket(len(compiled.flows), FLOW_BUCKET_MIN), cfg.n_planes,
        cfg.agg_mode)
    if chunk and not cfg.trace.enabled:
        cfg = replace(cfg, agg_mode="sparse", flow_chunk=chunk)
    return cfg


def _memo(caches: Dict, key: Tuple, name: str, build):
    """`caches[key]`, built under the span `name` on a miss."""
    value = caches.get(key)
    if value is not None:
        return value
    with span(name):
        value = caches[key] = build()
    return value


def _prepare(index: int, compiled, caches: Dict) -> _Point:
    cfg = _struct_cfg(compiled)
    spec = compiled.spec
    fa_key = (spec.topo, spec.tenants, spec.workloads, spec.workload_seed)

    def flow_arrays():
        fa = FlowArrays.build(compiled.flows, compiled.topo)
        engine._warn_f32_bytes(spec.name, fa, stacklevel=7)
        return fa
    fa = _memo(caches, ("fa", fa_key), "repro.prep.flow_arrays",
               flow_arrays)
    pm = getattr(compiled, "phase_mult", None)
    # phase-change slots join the segment boundaries, so the timeline
    # memo key folds them in ((0,) for every non-schedule point —
    # existing sharing is untouched)
    pb = tuple(engine.phase_boundaries(pm))
    r = spec.reaction
    react = cfg.react
    lag = reaction_lag(r, spec.sim.routing) if react else None
    # the reaction lag shapes both the visible snapshots and the
    # boundary set, so it joins the timeline memo key (None when the
    # reaction is off — existing sharing untouched)
    tl_key = (spec.faults, spec.sim.slots, spec.topo, spec.workload_seed,
              pb, lag)

    def timeline():
        tl = compile_fault_timeline(spec)
        vtl = None
        if react:
            vtl = lagged_timeline(tl, lag) if lag > 0 else tl
        boundaries = set(tl.change_slots()) | set(pb)
        if vtl is not None:
            boundaries |= set(vtl.change_slots())
        boundaries = tuple(sorted(boundaries))
        return (tl, boundaries, engine._seg_caps(tl, boundaries),
                engine._vis_seg_caps(vtl, boundaries, cfg.n_planes), vtl)
    tl, boundaries, caps, vcaps, vtl = _memo(
        caches, ("tl", tl_key), "repro.prep.timeline", timeline)
    routing, nic = spec.sim.routing, spec.sim.nic
    mode = r.mode if react else "instant"
    assign_key = assign = None
    if routing == "ecmp":
        assign_key = (fa_key, tl_key, compiled.cfg.seed, mode)
        assign = _memo(
            caches, ("assign", assign_key), "repro.prep.ecmp_replay",
            lambda: engine._assign_for(
                replace(cfg, routing="ecmp"), fa, tl, compiled.cfg.seed,
                boundaries, vtl=vtl, mode=mode,
                backup=getattr(compiled, "backup", None)))
    widths = _memo(
        caches, ("widths", fa_key, assign_key), "repro.prep.widths",
        lambda: engine._agg_widths(
            replace(cfg, routing=routing), fa,
            assign if assign is not None
            else np.zeros((1, len(fa), cfg.n_planes), np.int32)))
    return _Point(index=index, cfg=cfg, routing=routing, nic=nic,
                  fa_key=fa_key, tl_key=tl_key, assign_key=assign_key,
                  fa=fa, boundaries=boundaries, caps=caps, assign=assign,
                  widths=widths, dem=engine._seg_dem(pm, boundaries),
                  vcaps=vcaps)


def _pad_segs(a: np.ndarray, seg_b: int) -> np.ndarray:
    """Pad the leading segment axis to `seg_b` by replicating the last
    snapshot (never selected by `_seg_id`, which maps real slots only
    onto real segments)."""
    n = a.shape[0]
    if n == seg_b:
        return a
    return np.concatenate([a, np.repeat(a[-1:], seg_b - n, 0)])


def _padded_flow_cols(fa: FlowArrays, F_b: int, slots: int
                      ) -> Dict[str, np.ndarray]:
    """FlowBatch columns padded to the flow bucket.  Pad flows are
    inert: zero demand, infinite remaining bytes, start beyond the
    horizon, and `same_leaf` so they never touch the fabric."""
    F = len(fa)
    pad = F_b - F

    def p(a, fill):
        return np.concatenate([a, np.full(pad, fill, a.dtype)]) \
            if pad else a

    return {
        "src": p(fa.src, 0), "dst": p(fa.dst, 0),
        "src_leaf": p(fa.src_leaf, 0), "dst_leaf": p(fa.dst_leaf, 0),
        "demand": p(fa.demand, 0.0),
        "bytes_total": p(fa.bytes_total, np.inf),
        "start_slot": p(fa.start_slot, slots),
        "same_leaf": p(fa.src_leaf == fa.dst_leaf, True),
        "phase": p(fa.phase, 0),
    }


def _ecmp_plan(cfg: JxConfig, fa: FlowArrays, assign: np.ndarray,
               wu: int, F_b: int, seg_b: int) -> np.ndarray:
    """(seg_b, P, L*S + S*L, wu) ECMP load-aggregation plan — one table
    row, flow-padded to `F_b` (`engine._ecmp_load_plan`) and
    segment-padded to the bucket."""
    return _pad_segs(engine._ecmp_load_plan(cfg, fa, assign, wu, F_b),
                     seg_b)


def _carry0(B: int, F_b: int, cfg: JxConfig,
            remaining: np.ndarray) -> engine.SimCarry:
    """Batched initial scan carry (the donated argument), in the active
    x64 setting's dtypes."""
    from .state import NicCarry, SimCarry, probe_miss_dtype, stage_shapes
    x64 = bool(jax.config.jax_enable_x64)
    fdt = np.float64 if x64 else np.float32
    idt = np.int64 if x64 else np.int32
    (P, L, U), b_shape = stage_shapes(cfg)
    nic = NicCarry(
        rate=np.ones((B, F_b, P), fdt),
        alpha=np.zeros((B, F_b, P), fdt),
        probe_miss=np.zeros((B, F_b, P),
                            np.dtype(probe_miss_dtype(cfg, fdt))),
        eligible=np.ones((B, F_b, P), bool),
        pending_fail=np.zeros((B, F_b, P), idt))
    return SimCarry(
        q_up=np.zeros((B, P, L, U), fdt),
        q_down=np.zeros((B, P, U, L), fdt),
        q2_up=np.zeros((B,) + b_shape, fdt),
        q2_down=np.zeros((B,) + b_shape, fdt),
        nic=nic,
        remaining=remaining.astype(fdt),
        done=np.zeros((B, F_b), bool),
        completion=np.full((B, F_b), -1, idt),
        goodput_sum=np.zeros((B, F_b), fdt),
        util_up=np.zeros((B, P, L, U), fdt))


def _assemble_group(cfg: JxConfig, pts: List[_Point], caches: Dict,
                    n_devices: Optional[int] = None):
    """Assemble one structural group into the operands of a single
    launch.  Returns `(shards, lanes, args, metas)`: the shard count,
    the static lane layout, the program's operands, and the
    `(point_index, flow_arrays)` of every batch row.

    Elements are **lane-sorted** by routing branch: within a lane the
    `StackIdx.route` index is a concrete constant, so the engine traces
    only that routing branch for the lane instead of evaluating every
    branch batch-wide and selecting (`lax.switch`'s behavior under
    `vmap`).  NIC branches — cheap elementwise math — stay per-element
    traced switches, so a lane freely mixes all five NIC stacks (and
    ar/war, which share the pair lane via the traced `is_war` flag).
    Each lane is padded to a multiple of the device count with inert
    replicas of its last element; `finalize_group` drops them."""
    from .state import FlowBatch
    F_b = _bucket(max(len(p.fa) for p in pts), FLOW_BUCKET_MIN)
    if cfg.flow_chunk:
        # chunked runs reshape the flow axis to (chunks, chunk): round
        # the bucket up to a chunk multiple so the streamed scan needs
        # no extra tail pad (the rounding pad is the usual inert kind)
        F_b = -(-F_b // cfg.flow_chunk) * cfg.flow_chunk
    seg_b = _bucket(max(len(p.boundaries) for p in pts))
    widths = tuple(_bucket(m) for m in
                   map(max, zip(*(p.widths for p in pts))))
    wu = widths[3]
    P = cfg.n_planes
    sparse = cfg.agg_mode == "sparse"

    # deduplicated ECMP plan table; uid 0 = the inert all-pad plan that
    # pair-routed elements point at (its gathers read the zero row).
    # Sparse groups never gather a plan, so the table shrinks to one
    # inert cell.
    rows: List[np.ndarray] = [
        np.zeros((1, P, 1, 1), np.int32) if sparse else
        np.full((seg_b, P, engine._plan_rows(cfg), wu), F_b, np.int32)]
    row_uid: Dict[Tuple, int] = {}
    zero_assign = np.zeros((seg_b, F_b, P), np.int32)

    def elem(p: _Point) -> Dict:
        ckey = ("cols", p.fa_key, F_b, cfg.slots)
        cols = caches.get(ckey)
        if cols is None:
            cols = caches[ckey] = _padded_flow_cols(p.fa, F_b, cfg.slots)
        pkey = ("perms", p.fa_key, widths[:3], F_b)
        perms = caches.get(pkey)
        if perms is None:
            perms = caches[pkey] = engine._flow_perms(cfg, p.fa, widths,
                                                      F_b)
        uid = 0
        assign = zero_assign
        if p.routing == "ecmp":
            if not sparse:
                tkey = (p.assign_key, seg_b, wu, F_b)
                uid = row_uid.get(tkey)
                if uid is None:
                    uid = row_uid[tkey] = len(rows)
                    rows.append(_ecmp_plan(cfg, p.fa, p.assign, wu, F_b,
                                           seg_b))
            assign = _pad_segs(p.assign, seg_b)
            if len(p.fa) < F_b:
                assign = np.concatenate(
                    [assign, np.zeros((seg_b, F_b - len(p.fa), P),
                                      assign.dtype)], axis=1)
        skey = ("segcaps", p.tl_key, seg_b)
        padded = caches.get(skey)
        if padded is None:
            u, d, ac, u2, d2 = p.caps
            padded = caches[skey] = (
                _pad_segs(u, seg_b), _pad_segs(d, seg_b),
                _pad_segs(ac, seg_b), _pad_segs(u2, seg_b),
                _pad_segs(d2, seg_b),
                tuple(_pad_segs(v, seg_b) for v in p.vcaps),
                engine._seg_id(p.boundaries, cfg.slots))
        # phase-demand snapshots: segment-padded like the capacity
        # snapshots, lane-padded with 1.0 to the group's phase bucket
        # (no flow carries a padded phase id)
        K_b = max(cfg.n_phases, 1)
        dem = _pad_segs(p.dem, seg_b)
        if dem.shape[1] < K_b:
            dem = np.concatenate(
                [dem, np.ones((seg_b, K_b - dem.shape[1]), dem.dtype)],
                axis=1)
        return {"index": p.index, "fa": p.fa, "cols": cols,
                "perms": perms, "uid": uid, "assign": assign,
                "caps": padded, "dem": dem,
                "stack": stack_idx_for(p.routing, p.nic)}

    n_dev = n_devices or len(jax.devices())
    shards = min(len(pts), n_dev) if n_dev > 1 and len(pts) > 1 else 1

    # lane-sort: per route, pad the lane to a multiple of the shard
    # count, then deal each lane's chunks out device-major so every
    # device sees the same static (route, count) layout
    lane_elems: Dict[int, List[Dict]] = {}
    for p in pts:
        lane_elems.setdefault(stack_idx_for(p.routing, p.nic)[0],
                              []).append(elem(p))
    lanes = []
    for route in sorted(lane_elems):
        es = lane_elems[route]
        pad = -len(es) % shards
        es += [dict(es[-1], index=-1)] * pad      # inert replicas
        lanes.append((route, len(es) // shards))
    seq: List[Dict] = []
    for d in range(shards):
        for route, n in lanes:
            seq += lane_elems[route][d * n:(d + 1) * n]
    lanes_static = tuple(lanes)

    B = len(seq)
    fb = FlowBatch(**{k: np.stack([e["cols"][k] for e in seq])
                      for k in seq[0]["cols"]})
    aggs = engine._AggPerms(
        src=np.stack([e["perms"][0] for e in seq]),
        dst=np.stack([e["perms"][1] for e in seq]),
        pair=np.stack([e["perms"][2] for e in seq]),
        ecmp_load=np.zeros((B, 1, 1, 1, 1), np.int32))  # table instead
    table = np.stack(rows)
    stack = StackIdx(
        route=np.array([e["stack"][0] for e in seq], np.int32),
        is_war=np.array([e["stack"][1] for e in seq], bool),
        nic=np.array([e["stack"][2] for e in seq], np.int32),
        is_esr=np.array([e["stack"][3] for e in seq], bool))
    carry0 = _carry0(B, F_b, cfg, fb.bytes_total)
    mapped = (stack, carry0, fb,
              np.stack([e["caps"][0] for e in seq]),
              np.stack([e["caps"][1] for e in seq]),
              np.stack([e["caps"][2] for e in seq]),
              np.stack([e["caps"][3] for e in seq]),
              np.stack([e["caps"][4] for e in seq]),
              np.stack([e["dem"] for e in seq]),
              np.stack([e["caps"][5][0] for e in seq]),
              np.stack([e["caps"][5][1] for e in seq]),
              np.stack([e["caps"][5][2] for e in seq]),
              np.stack([e["caps"][5][3] for e in seq]),
              np.stack([e["assign"] for e in seq]), aggs,
              np.array([e["uid"] for e in seq], np.int32),
              np.stack([e["caps"][6] for e in seq]))
    # multi-shard groups stay flat (B, ...): the mesh-sharded program
    # reshapes to (shards, B//shards, ...) internally, and `seq` is
    # already dealt device-major so the flat order is shard-major
    metas = [(e["index"], e["fa"]) for e in seq]
    return shards, lanes_static, mapped + (table,), metas


def _dispatch_group(cfg: JxConfig, pts: List[_Point], caches: Dict,
                    n_devices: Optional[int] = None):
    """Launch one structural group as a single program (see
    `_assemble_group`).  Counts the launch's flow-slots, real
    (`flow_slots_real`: real flows of real rows) and launched
    (`flow_slots_launched`: the flow bucket over every batch row, lane
    pad replicas included), the launched flow-slots of ECMP rows whose
    link lookups contract one-hots (`ecmp_onehot_flow_slots`, 0 where
    they gather; see `engine.ecmp_onehot`), the launched flow-slots of
    rows whose host-port sums contract one-hots (`onehot_agg_flow_slots`,
    every row or none; see `engine.onehot_agg`), the launched flow-slots
    of rows that route by leaf pair, ar or war (`pair_route_flow_slots`,
    from each row's own routing), and the operand bytes it hands the
    device (`launch_bytes`)."""
    with span("repro.launch"):
        shards, lanes, args, metas = _assemble_group(cfg, pts, caches,
                                                     n_devices)
        engine._record_launch("mega", (cfg, shards, lanes), args)
        with warnings.catch_warnings():
            # the scan rewrites the whole donated carry, but only 4 of
            # its leaves alias a program output — jax warns about the
            # rest on every first compile, which is expected here, not
            # actionable
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out = engine._jitted_mb(cfg, shards, lanes)(*args)
    F_b = args[2].demand.shape[1]
    count("flow_slots_real",
          cfg.slots * sum(len(fa) for index, fa in metas if index >= 0))
    count("flow_slots_launched", cfg.slots * len(metas) * F_b)
    ecmp_rows = shards * sum(n for route, n in lanes
                             if route == engine.ROUTE_ECMP)
    count("ecmp_onehot_flow_slots",
          cfg.slots * ecmp_rows * F_b
          if engine.ecmp_onehot(cfg, F_b) else 0)
    count("onehot_agg_flow_slots",
          cfg.slots * len(metas) * F_b if engine.onehot_agg(cfg, F_b) else 0)
    count("pair_route_flow_slots", cfg.slots * F_b * int(
        np.count_nonzero(args[0].route == engine.ROUTE_PAIR)))
    count("launch_bytes", sum(a.nbytes for a in jax.tree.leaves(args)))
    return cfg, metas, [p.index for p in pts], shards, out


def plan_megabatch(points: List) -> Tuple[Dict, List[List[Tuple]]]:
    """Cheap structural pre-grouping of `CompiledScenario`s: bucket by
    `(struct cfg, flow bucket)` *without* building flow arrays or fault
    timelines.  Returns `(caches, planned)` where each planned group is
    `[(point_index, compiled), ...]` ready for `dispatch_planned` —
    this is the unit the executor pipelines (host prep of group k+1
    overlapping device execution of group k)."""
    engine._BACKEND_USED = True
    caches: Dict = {}
    groups: Dict[Tuple, List[Tuple]] = {}
    order: List[Tuple] = []
    with span("repro.plan"):
        for i, c in enumerate(points):
            key = (_struct_cfg(c), _bucket(len(c.flows), FLOW_BUCKET_MIN))
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append((i, c))
    return caches, [groups[k] for k in order]


def _sub_groups(group: List[Tuple], caches: Dict
                ) -> List[Tuple[JxConfig, List[_Point]]]:
    """Memoized `_prepare` of every member, sub-split by the complete
    structural key (fault-timeline segment counts only become known
    here)."""
    prepared = []
    for i, c in group:
        with span("repro.prep.point"):
            prepared.append(_prepare(i, c, caches))
    sub: Dict[Tuple, List[_Point]] = {}
    order: List[Tuple] = []
    for p in prepared:
        key = (p.cfg, _bucket(len(p.fa), FLOW_BUCKET_MIN),
               _bucket(len(p.boundaries)))
        if key not in sub:
            sub[key] = []
            order.append(key)
        sub[key].append(p)
    return [(key[0], sub[key]) for key in order]


def dispatch_planned(group: List[Tuple], caches: Dict,
                     n_devices: Optional[int] = None) -> List:
    """Full host prep + launch for one planned group: one launch per
    complete structural key.  `n_devices` caps the lane mesh (default:
    every visible device).  Returns `[(point_indices, handle)]` entries
    for `finalize_group`."""
    with span("repro.dispatch"):
        return [([p.index for p in pts],
                 _dispatch_group(cfg, pts, caches, n_devices))
                for cfg, pts in _sub_groups(group, caches)]


def megabatch_programs(points: List, n_devices: Optional[int] = None
                       ) -> List[Tuple]:
    """The `(jitted program, operands)` that `dispatch_megabatch` would
    launch for these `CompiledScenario`s, without launching anything —
    for inspecting a program (`fn.lower(*args).compile()`), or compiling
    it for a device described by shapes alone."""
    caches, planned = plan_megabatch(points)
    out = []
    for group in planned:
        for cfg, pts in _sub_groups(group, caches):
            shards, lanes, args, _ = _assemble_group(cfg, pts, caches,
                                                     n_devices)
            out.append((engine._jitted_mb(cfg, shards, lanes), args))
    return out


def dispatch_megabatch(points: List,
                       n_devices: Optional[int] = None) -> List:
    """Group `CompiledScenario`s by structural key and launch each group
    as ONE fused program (all groups dispatched before any is awaited —
    JAX CPU execution is async).  Returns `[(point_indices, handle)]`
    for `finalize_group`.  A homogeneous-topology grid — however many
    routing/nic/fault/seed axes it sweeps — is a single group.  This is
    the sequential composition of `plan_megabatch` + `dispatch_planned`;
    the executor's pipelined path calls the two halves itself."""
    caches, planned = plan_megabatch(points)
    out: List = []
    for group in planned:
        out.extend(dispatch_planned(group, caches, n_devices))
    return out


def finalize_group(handle) -> List[JxSimResult]:
    """Block on one `_dispatch_group` handle (span
    `repro.finalize.wait`) and unpack its per-point results
    (`repro.finalize.unpack`)."""
    cfg, metas, order, shards, out = handle
    with span("repro.finalize"):
        with span("repro.finalize.wait"):
            jax.block_until_ready(out)
        with span("repro.finalize.unpack"):
            return _unpack(cfg, metas, order, out)


def _unpack(cfg: JxConfig, metas: List[Tuple], order: List[int],
            out) -> List[JxSimResult]:
    """Per-point results of a finished launch, dropping lane padding and
    flow-bucket padding and undoing the lane sort (results come back in
    the group's point order)."""
    outs = [np.asarray(o) for o in out]
    by_index = {}
    for b, (index, fa) in enumerate(metas):
        if index < 0 or index in by_index:      # lane pad replica
            continue
        F = len(fa)
        row = [o[b] for o in outs]
        mean_goodput, completion, totals, util = row[:4]
        point_out = [mean_goodput[:F], completion[:F], totals, util]
        tail = 4
        if cfg.react:
            point_out.append(row[tail])       # blackhole timeline (T,)
            tail += 1
        # trace tail: flow-axis fields carry the bucket padding on axis 1
        # (after time); pad flows are inert, so slicing recovers the
        # unpadded capture exactly
        for name, arr in zip(cfg.trace.active_fields(), row[tail:]):
            point_out.append(arr[:, :F] if name in FLOW_AXIS_FIELDS
                             else arr)
        by_index[index] = engine._wrap(cfg, fa, point_out)
    return [by_index[i] for i in order]


def run_megabatch(points: List, n_devices: Optional[int] = None
                  ) -> List[JxSimResult]:
    """Simulate arbitrary `CompiledScenario` grid points with the fewest
    possible launches (one per structural group), returning results in
    point order.  `n_devices` caps the lane mesh (default: every visible
    device) — e.g. 1 to rerun a sharded grid on a single device."""
    results: List = [None] * len(points)
    for idxs, handle in dispatch_megabatch(points, n_devices):
        for i, r in zip(idxs, finalize_group(handle)):
            results[i] = r
    return results
