"""Lower a `ScenarioSpec` to the `(topo, flows, events)` triple that
`netsim.sim.run_sim` consumes.

Compilation is deterministic: the same (spec, workload_seed) produces
byte-identical flow lists and an events closure with identical effects.
All randomness flows through one `np.random.default_rng(workload_seed)`
consumed in declaration order (tenants first, then workloads), plus one
derived per-fault stream for 'random_fail'.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.fault_tolerance import poisson_flaps
from repro.netsim.fabric import Flow
from repro.netsim.flight import span
from repro.netsim.sim import SimConfig, SimResult, run_sim
from repro.netsim.topology import (Fabric, FatTree, LeafSpine,
                                   backup_path_table)
from repro.netsim.workloads import (all2all, bisection_pairs, one_to_many,
                                    ring_neighbors)

from .spec import (FaultSpec, ScenarioSpec, TenantSpec, WorkloadSpec,
                   fault_planes, fault_transition_slots, flap_phase,
                   reaction_lag)


@dataclass
class CompiledScenario:
    """Single-use run bundle: `topo` is mutated in place by `events` on
    the NumPy backend, so compile again (cheap) for a fresh run."""
    spec: ScenarioSpec
    topo: Fabric
    flows: List[Flow]
    cfg: SimConfig
    events: Callable[[int, Fabric], None]
    tenants: Dict[str, List[int]]
    fault_slots: Tuple[Tuple[int, str], ...]   # (slot, label), sorted
    # schedule workloads only: (slots, K) demand-multiplier timeline
    # (lane 0 always 1.0) + per-schedule `comms.TrainSchedule` metadata
    phase_mult: Optional[np.ndarray] = None
    schedules: Tuple = ()
    # failure-reaction lowering (spec.reaction with a non-zero lag):
    # a second pristine fabric the event closures replay into `lag`
    # slots late — routing steers against it.  `backup` is the
    # precomputed fast-reroute successor table (mode='backup').
    vis_topo: Optional[Fabric] = None
    backup: Optional[np.ndarray] = None

    def run(self, backend: Optional[str] = None):
        """Simulate.  `backend` overrides the spec's `sim.backend`;
        'jax' lowers the fault schedule to a static timeline and runs the
        jitted engine (lazy import keeps NumPy pool workers JAX-free)."""
        backend = backend or self.cfg.backend
        if backend == "jax":
            from repro.netsim.jx.engine import run_compiled
            return run_compiled(self)
        if backend != "numpy":
            raise ValueError(
                f"unknown backend {backend!r}; expected 'numpy' or 'jax'")
        if self.spec.reaction is None:
            # pre-reaction call shape, byte-identical
            return run_sim(self.topo, self.flows, self.cfg,
                           events=self.events, phase_mult=self.phase_mult)
        return run_sim(
            self.topo, self.flows, self.cfg, events=self.events,
            phase_mult=self.phase_mult, reaction=self.spec.reaction,
            vis_topo=self.vis_topo,
            vis_events=self.events if self.vis_topo is not None else None,
            backup=self.backup)


# ---------------------------------------------------------------------------
# tenants
# ---------------------------------------------------------------------------

def resolve_tenants(spec: ScenarioSpec, rng: np.random.Generator
                    ) -> Dict[str, List[int]]:
    n = spec.topo.n_hosts
    taken: set = set()
    out: Dict[str, List[int]] = {}
    for t in spec.tenants:
        if t.placement == "explicit":
            hosts = list(t.hosts)
        elif t.placement == "block":
            count = n - t.offset if t.n_hosts is None else t.n_hosts
            hosts = list(range(t.offset, t.offset + count))
        elif t.placement == "interleave":
            hosts = list(range(t.offset, n, t.stride))
            if t.n_hosts is not None:
                hosts = hosts[:t.n_hosts]
        elif t.placement == "random":
            pool = np.array(sorted(set(range(n)) - taken))
            count = len(pool) if t.n_hosts is None else t.n_hosts
            hosts = sorted(int(h) for h in
                           rng.choice(pool, size=count, replace=False))
        elif t.placement == "remainder":
            hosts = sorted(set(range(n)) - taken)
            if t.n_hosts is not None:
                hosts = hosts[:t.n_hosts]
        else:                                          # pragma: no cover
            raise ValueError(t.placement)
        if len(set(hosts)) != len(hosts):
            dupes = sorted({h for h in hosts if hosts.count(h) > 1})
            raise ValueError(
                f"{spec.name}: tenant {t.name} lists hosts {dupes} "
                "more than once")
        clash = taken & set(hosts)
        if clash:
            raise ValueError(
                f"{spec.name}: tenant {t.name} overlaps hosts {clash}")
        bad = [h for h in hosts if not 0 <= h < n]
        if bad:
            raise ValueError(
                f"{spec.name}: tenant {t.name} hosts {bad} outside "
                f"[0, {n})")
        taken |= set(hosts)
        out[t.name] = hosts
    return out


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------

def _build_workload(w: WorkloadSpec, topo: LeafSpine, hosts: List[int],
                    rng: np.random.Generator, group: str) -> List[Flow]:
    if w.kind == "bisection":
        flows = bisection_pairs(topo, hosts, rng, group=group)
        for f in flows:
            f.demand *= w.demand
            f.bytes_total = w.bytes_total
        return flows
    if w.kind == "all2all":
        flows = all2all(topo, hosts, group=group,
                        bytes_per_pair=w.bytes_total)
        for f in flows:
            f.demand *= w.demand
        return flows
    if w.kind == "allreduce":
        flows = ring_neighbors(hosts, group=group,
                               bytes_per_hop=w.bytes_total)
        for f in flows:
            f.demand *= w.demand
        return flows
    if w.kind == "incast":
        sinks, srcs = hosts[:w.sinks], hosts[w.sinks:]
        return [Flow(int(a), int(b), w.demand, w.bytes_total, group=group)
                for a in srcs for b in sinks]
    if w.kind == "permutation":
        order = rng.permutation(hosts)
        return [Flow(int(order[i]), int(order[(i + 1) % len(order)]),
                     w.demand, w.bytes_total, group=group)
                for i in range(len(order))]
    if w.kind == "storage":
        flows = []
        arr = np.asarray(hosts)
        for h in hosts:
            peers = arr[arr != h]
            dsts = rng.choice(peers, size=min(w.fanout, len(peers)),
                              replace=False)
            flows += [Flow(int(h), int(d), w.demand, w.bytes_total,
                           group=group) for d in dsts]
        return flows
    if w.kind == "one2many":
        srcs, dsts = hosts[:w.srcs], hosts[w.srcs:]
        if not dsts:
            raise ValueError(
                f"one2many workload for tenant {w.tenant!r}: srcs="
                f"{w.srcs} leaves no destination hosts")
        flows = one_to_many(topo, srcs, dsts, group=group,
                            bytes_per_flow=w.bytes_total)
        for f in flows:
            f.demand *= w.demand
        return flows
    if w.kind == "pairs":
        foreign = sorted({h for p in w.pairs for h in p} - set(hosts))
        if foreign:
            raise ValueError(
                f"pairs workload for tenant {w.tenant!r} references "
                f"hosts {foreign} outside the tenant")
        return [Flow(int(a), int(b), w.demand, w.bytes_total, group=group)
                for a, b in w.pairs]
    raise ValueError(f"unknown workload kind {w.kind!r}")


def build_flows(spec: ScenarioSpec, topo: LeafSpine,
                tenants: Dict[str, List[int]],
                rng: np.random.Generator
                ) -> Tuple[List[Flow], Optional[np.ndarray], Tuple]:
    """Lower every workload.  Returns `(flows, phase_mult, schedules)`:
    `phase_mult` is the (slots, K) demand-multiplier timeline (None when
    no schedule workload is present) and `schedules` the matching
    `comms.TrainSchedule` metadata, flow indices already rebased onto
    the global flow list.  Multiple schedule workloads stack their lanes
    column-wise; lane 0 stays the shared always-1.0 lane."""
    flows: List[Flow] = []
    pm: Optional[np.ndarray] = None
    schedules: List = []
    for w in spec.workloads:
        group = w.group or w.tenant
        if w.kind == "schedule":
            # Lazy import: `repro.comms` pulls in JAX for parameter
            # pytrees; NumPy pool workers stay JAX-free otherwise.
            from repro.comms import lower_schedule
            lane_off = 0 if pm is None else pm.shape[1] - 1
            fl, wpm, sched = lower_schedule(
                w, tenants[w.tenant], spec.topo, spec.sim, group,
                lane_offset=lane_off)
            schedules.append(sched.shifted(len(flows)))
            pm = wpm if pm is None else np.concatenate(
                [pm, wpm[:, 1:]], axis=1)
            flows += fl          # start slots are schedule-internal
            continue
        fl = _build_workload(w, topo, tenants[w.tenant], rng, group)
        if w.start_slot:
            for f in fl:
                f.start_slot = w.start_slot
        flows += fl
    return flows, pm, tuple(schedules)


# ---------------------------------------------------------------------------
# fault schedule -> events closure
# ---------------------------------------------------------------------------

def _planes(f: FaultSpec, topo: Fabric) -> List[int]:
    return list(fault_planes(f, topo.n_planes))


def _fail_random_link(topo: Fabric, p: int, rng: np.random.Generator,
                      frac: float) -> None:
    """One uniformly-drawn fabric-link kill for random_fail's exact-k
    mode.  Draw-for-draw shared semantics with the jx timeline compiler
    (`netsim.jx.events._apply_fault`): leaf_spine draws (leaf, spine);
    fat_tree draws one index over leaf–agg links followed by pod–core
    links."""
    if topo.kind == "leaf_spine":
        topo.fail_uplink(p, int(rng.integers(topo.n_leaves)),
                         int(rng.integers(topo.n_spines)), frac)
        return
    L, A = topo.n_leaves, topo.n_aggs
    n_stage_a = L * A
    idx = int(rng.integers(n_stage_a + topo.n_pods * topo.n_cores))
    if idx < n_stage_a:
        topo.fail_uplink(p, idx // A, idx % A, frac)
    else:
        rem = idx - n_stage_a
        topo.fail_core_link(p, rem // topo.n_cores, rem % topo.n_cores,
                            frac)


def _flap(t: int, f: FaultSpec, fail, restore) -> None:
    """Periodic kill/restore for *_flap faults (phase math shared with
    the JAX timeline compiler via `spec.flap_phase`)."""
    ph = flap_phase(t, f)
    if ph == "fail":
        fail()
    elif ph == "restore":
        restore()


def poisson_flap_schedule(spec: ScenarioSpec, index: int
                          ) -> Tuple[Tuple[int, int, int, int], ...]:
    """Slot-level schedule for a kind='poisson_flap' fault: sorted
    `(down_slot, up_slot, plane, link)` rows.  The §6.6 MTBF methodology
    (`core.fault_tolerance.poisson_flaps`) draws per-link exponential
    inter-arrivals so the *fleet* (every fabric link on every selected
    plane) flaps `flaps_per_min` times per minute; draws are seeded by
    `(workload_seed, 6007, fault_index)` so the event-closure path and
    the JAX timeline compiler replay the identical schedule.

    `link` indexes leaf–spine uplinks row-major on leaf_spine and, on
    fat_tree, leaf–agg links followed by pod–core links (the same decode
    as random_fail's exact-k draws).  `up_slot = down_slot + down_slots`
    exactly — duration converts through whole slots, so no float
    boundary can disagree between backends."""
    f = spec.faults[index]
    topo = spec.topo
    planes = list(fault_planes(f, topo.n_planes))
    if topo.kind == "fat_tree":
        n_links = (topo.n_leaves * topo.n_aggs
                   + topo.n_pods * topo.n_cores)
    else:
        n_links = topo.n_leaves * topo.n_spines
    slot_s = spec.sim.slot_us * 1e-6
    stop = spec.sim.slots if f.stop_slot is None \
        else min(f.stop_slot, spec.sim.slots)
    window = stop - f.start_slot
    if window <= 0:
        return ()
    rng = np.random.default_rng((spec.workload_seed, 6007, index))
    evs = poisson_flaps(rng, len(planes) * n_links, f.flaps_per_min,
                        duration_s=f.down_slots * slot_s,
                        horizon_s=window * slot_s)
    out = []
    for ev in evs:
        dn = f.start_slot + int(ev.t_down // slot_s)
        out.append((dn, dn + f.down_slots,
                    planes[ev.link // n_links], ev.link % n_links))
    return tuple(sorted(out))


def apply_poisson_flap(t: int, f: FaultSpec, sched, topo: Fabric) -> None:
    """Apply one slot of a poisson_flap schedule to a runtime fabric.
    Restores run before kills so a back-to-back flap re-kills; schedule
    order is fixed, so both backends mutate identically.  Restore sets
    the link back to its full capacity (link_flap semantics) even if
    outages overlapped."""
    L = topo.n_leaves
    A = topo.n_aggs if topo.kind == "fat_tree" else topo.n_spines
    n_stage_a = L * A

    def place(link):
        if topo.kind != "fat_tree" or link < n_stage_a:
            return "a", link // A, link % A
        rem = link - n_stage_a
        return "b", rem // topo.n_cores, rem % topo.n_cores

    for dn, up, p, link in sched:
        if t != up:
            continue
        stage, x, y = place(link)
        if stage == "a":
            cap = topo.link_cap * topo.parallel_links
            topo.up[p, x, y] = cap
            topo.down[p, y, x] = cap
        else:
            topo.up2[p, x, y] = topo.core_cap
            topo.down2[p, x, y] = topo.core_cap
    for dn, up, p, link in sched:
        if t != dn:
            continue
        stage, x, y = place(link)
        if stage == "a":
            topo.fail_uplink(p, x, y, f.frac)
        else:
            topo.fail_core_link(p, x, y, f.frac)


def make_events(spec: ScenarioSpec
                ) -> Tuple[Callable[[int, Fabric], None],
                           Tuple[Tuple[int, str], ...]]:
    cap_link = spec.topo.uplink_cap
    cap_acc = spec.topo.access_cap
    faults = spec.faults
    # per-fault derived streams so 'random_fail' draws don't depend on
    # how many other faults exist or fire first
    fail_seeds = {i: (spec.workload_seed, 7919, i)
                  for i, f in enumerate(faults) if f.kind == "random_fail"}
    scheds = {i: poisson_flap_schedule(spec, i)
              for i, f in enumerate(faults) if f.kind == "poisson_flap"}

    def _restore_uplink(topo, p, leaf, spine):
        topo.up[p, leaf, spine] = cap_link
        topo.down[p, spine, leaf] = cap_link

    def events(t: int, topo: Fabric) -> None:
        for i, f in enumerate(faults):
            if f.kind == "link_kill":
                if t == f.start_slot:
                    for p in _planes(f, topo):
                        topo.fail_uplink(p, f.leaf, f.spine, f.frac)
                elif f.stop_slot is not None and t == f.stop_slot:
                    for p in _planes(f, topo):
                        _restore_uplink(topo, p, f.leaf, f.spine)
            elif f.kind == "link_flap":
                _flap(t, f,
                      lambda: [topo.fail_uplink(p, f.leaf, f.spine, f.frac)
                               for p in _planes(f, topo)],
                      lambda: [_restore_uplink(topo, p, f.leaf, f.spine)
                               for p in _planes(f, topo)])
            elif f.kind == "access_kill":
                if t == f.start_slot:
                    for p in _planes(f, topo):
                        topo.fail_access(p, f.host)
                elif f.stop_slot is not None and t == f.stop_slot:
                    for p in _planes(f, topo):
                        topo.restore_access(p, f.host)
            elif f.kind == "access_flap":
                _flap(t, f,
                      lambda: [topo.fail_access(p, f.host)
                               for p in _planes(f, topo)],
                      lambda: [topo.restore_access(p, f.host)
                               for p in _planes(f, topo)])
            elif f.kind == "cascade":
                for j, s in enumerate(f.spines):
                    if t == f.start_slot + j * f.period:
                        for p in _planes(f, topo):
                            if topo.kind == "fat_tree":
                                # whole-switch loss: the agg's leaf AND
                                # core links die together
                                topo.fail_agg(p, f.pod, s)
                            else:
                                topo.up[p, :, s] = 0.0
                                topo.down[p, s, :] = 0.0
            elif f.kind == "straggler":
                if t == f.start_slot:
                    for p in _planes(f, topo):
                        topo.access[p, f.host] = cap_acc * f.frac
                elif f.stop_slot is not None and t == f.stop_slot:
                    for p in _planes(f, topo):
                        topo.access[p, f.host] = cap_acc
            elif f.kind == "leaf_trim":
                if t == f.start_slot:
                    for p in _planes(f, topo):
                        topo.trim_leaf_uplinks(p, f.leaf, f.frac)
            elif f.kind == "random_fail":
                if t == f.start_slot:
                    rng = np.random.default_rng(fail_seeds[i])
                    if f.count:
                        # exact-k mode: `count` fabric-link draws per
                        # plane (repeats compound, like the Fig 14a
                        # proxy); on fat_tree both stages are in the
                        # draw population
                        for p in _planes(f, topo):
                            for _ in range(f.count):
                                _fail_random_link(topo, p, rng, f.frac)
                    else:
                        topo.random_link_failures(rng, f.frac)
            elif f.kind == "core_kill":
                if t == f.start_slot:
                    for p in _planes(f, topo):
                        topo.fail_core_link(p, f.pod, f.core, f.frac)
                elif f.stop_slot is not None and t == f.stop_slot:
                    for p in _planes(f, topo):
                        topo.up2[p, f.pod, f.core] = topo.core_cap
                        topo.down2[p, f.pod, f.core] = topo.core_cap
            elif f.kind == "poisson_flap":
                apply_poisson_flap(t, f, scheds[i], topo)

    slots = sorted(
        {sl for i, f in enumerate(faults)
         for sl in fault_transition_slots(f, spec.sim.slots,
                                          sched=scheds.get(i))},
        key=lambda x: (x[0], x[1]))
    return events, tuple(slots)


# ---------------------------------------------------------------------------
# top level
# ---------------------------------------------------------------------------

def build_topology(ts) -> Fabric:
    """Instantiate the runtime fabric a `TopologySpec` describes."""
    if ts.kind == "fat_tree":
        return FatTree(
            n_pods=ts.n_pods, leaves_per_pod=ts.leaves_per_pod,
            n_aggs=ts.n_aggs, n_cores=ts.n_cores,
            hosts_per_leaf=ts.hosts_per_leaf, n_planes=ts.n_planes,
            parallel_links=ts.parallel_links, link_cap=ts.link_cap,
            core_link_cap=ts.core_link_cap, access_cap=ts.access_cap)
    return LeafSpine(
        n_leaves=ts.n_leaves, n_spines=ts.n_spines,
        hosts_per_leaf=ts.hosts_per_leaf, n_planes=ts.n_planes,
        parallel_links=ts.parallel_links, link_cap=ts.link_cap,
        access_cap=ts.access_cap)


def compile_scenario(spec: ScenarioSpec) -> CompiledScenario:
    spec.validate()
    topo = build_topology(spec.topo)
    rng = np.random.default_rng(spec.workload_seed)
    tenants = resolve_tenants(spec, rng)
    with span("repro.prep.flows"):
        flows, phase_mult, schedules = build_flows(spec, topo, tenants,
                                                   rng)
    if not flows:
        raise ValueError(f"{spec.name}: scenario compiled to zero flows")
    events, fault_slots = make_events(spec)
    cfg = SimConfig(
        slots=spec.sim.slots, slot_us=spec.sim.slot_us,
        routing=spec.sim.routing, nic=spec.sim.nic,
        base_rtt_us=spec.sim.base_rtt_us,
        warmup_frac=spec.sim.warmup_frac,
        sw_lb_delay_ms=spec.sim.sw_lb_delay_ms,
        seed=spec.sim.seed, record_every=spec.sim.record_every,
        backend=spec.sim.backend, trace=spec.sim.trace)
    vis_topo = backup = None
    if spec.reaction is not None and spec.reaction.enabled:
        if reaction_lag(spec.reaction, spec.sim.routing) > 0:
            # pristine twin for the lagged routing view; the shared
            # events closure replays into it `lag` slots late
            vis_topo = build_topology(spec.topo)
        if spec.reaction.mode == "backup":
            cpa = (spec.topo.n_cores // spec.topo.n_aggs
                   if spec.topo.kind == "fat_tree" else 1)
            backup = backup_path_table(spec.topo.kind, spec.topo.n_paths,
                                       cores_per_agg=cpa)
    return CompiledScenario(spec=spec, topo=topo, flows=flows, cfg=cfg,
                            events=events, tenants=tenants,
                            fault_slots=fault_slots,
                            phase_mult=phase_mult, schedules=schedules,
                            vis_topo=vis_topo, backup=backup)


def run_scenario(spec: ScenarioSpec) -> SimResult:
    """Compile + simulate in one call (fresh topology every time)."""
    return compile_scenario(spec).run()
