"""Plain reference: a fluid multiplane leaf-spine simulator in NumPy.

It runs one grid point of a benchmark configuration from the same data
the harness hands the program (`configs/*.json`, a traffic point's
routing / NIC / fault fraction / seeds) and imports nothing of the
program.  It is a trimmed copy of the float64 NumPy engine that the
program ships as its own reference (`netsim/sim.py`, `fabric.py`,
`cc.py`, `topology.py` and the scenario compiler's flow and fault
generation), kept here so that a change to the program cannot move the
yardstick.  Only what the benchmark's configurations use is kept:
leaf-spine fabrics, block tenants, incast and storage workloads,
`link_flap` and exact-k `random_fail` faults, ar / war / ecmp routing,
and the spx / dcqcn NIC stacks.

`precision="bf16"` is the control: every array the slot step produces
is rounded to bfloat16 (round to nearest even) before it is used again.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

# fabric and NIC constants of the modelled system (paper §4, §6)
ECN_QUEUE_THRESH = 3.0
AR_TEMPERATURE = 0.25
JSQ_BINS = 16
Q_CAP = 64.0
SPX_MD = 0.7
SPX_AI = 0.08
SPX_RTT_GAIN = 0.15
DCQCN_ALPHA_G = 0.0625
DCQCN_AI = 0.01
MIN_RATE = 0.01
TARGET_RTT_US = 12.0
PROBE_TIMEOUT = 3
EPS = 1e-12


def bf16(x):
    """Round float64 values to the nearest bfloat16 (ties to even),
    returned as float64."""
    a = np.asarray(x, np.float64).astype(np.float32)
    b = a.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    out = b.astype(np.uint32).view(np.float32).astype(np.float64)
    out = np.where(np.isfinite(a), out, a)
    return out if np.ndim(x) else float(out)


def _identity(x):
    return x


@dataclass
class Point:
    """One grid point: a configuration plus the traffic's choices."""
    config: Dict
    routing: str
    nic: str
    fault_frac: float
    kills: int
    sim_seed: int
    workload_seed: int

    @property
    def slots(self) -> int:
        return int(self.config["sim"]["slots"])


# ---------------------------------------------------------------------------
# topology, flows, faults
# ---------------------------------------------------------------------------

class Fabric:
    def __init__(self, topo: Dict):
        self.L = topo["n_leaves"]
        self.S = topo["n_spines"]
        self.hpl = topo["hosts_per_leaf"]
        self.P = topo["n_planes"]
        self.H = self.L * self.hpl
        self.cap = topo["link_cap"] * topo["parallel_links"]
        self.up = np.full((self.P, self.L, self.S), self.cap)
        self.down = np.full((self.P, self.S, self.L), self.cap)
        self.access = np.full((self.P, self.H), topo["access_cap"])

    def fail_uplink(self, p, leaf, spine, frac):
        self.up[p, leaf, spine] *= (1.0 - frac)
        self.down[p, spine, leaf] *= (1.0 - frac)

    def restore_uplink(self, p, leaf, spine):
        self.up[p, leaf, spine] = self.cap
        self.down[p, spine, leaf] = self.cap

    def path_capacity(self, src_leaf, dst_leaf):
        """(F, P, S) min capacity along each spine path."""
        cap = np.minimum(self.up[:, src_leaf, :],
                         np.swapaxes(self.down, 1, 2)[:, dst_leaf, :])
        return cap.transpose(1, 0, 2)


def tenant_hosts(cfg: Dict) -> List[int]:
    t = cfg["tenant"]
    n = cfg["topology"]["n_leaves"] * cfg["topology"]["hosts_per_leaf"]
    count = n - t["offset"] if t.get("n_hosts") is None else t["n_hosts"]
    return list(range(t["offset"], t["offset"] + count))


def flow_count(cfg: Dict) -> int:
    """Real flows of one point (no bucket padding)."""
    w, hosts = cfg["workload"], tenant_hosts(cfg)
    if w["kind"] == "incast":
        return (len(hosts) - w["sinks"]) * w["sinks"]
    if w["kind"] == "storage":
        return len(hosts) * min(w["fanout"], len(hosts) - 1)
    raise ValueError(f"unknown workload kind {w['kind']!r}")


def build_flows(cfg: Dict, workload_seed: int):
    """(src, dst, demand) arrays in the scenario compiler's flow order,
    drawing from one `default_rng(workload_seed)` as it does."""
    w, hosts = cfg["workload"], tenant_hosts(cfg)
    rng = np.random.default_rng(workload_seed)
    if w["kind"] == "incast":
        sinks, srcs = hosts[:w["sinks"]], hosts[w["sinks"]:]
        pairs = [(a, b) for a in srcs for b in sinks]
    elif w["kind"] == "storage":
        arr = np.asarray(hosts)
        pairs = []
        for h in hosts:
            peers = arr[arr != h]
            dsts = rng.choice(peers, size=min(w["fanout"], len(peers)),
                              replace=False)
            pairs += [(h, int(d)) for d in dsts]
    else:
        raise ValueError(f"unknown workload kind {w['kind']!r}")
    src = np.array([a for a, _ in pairs], np.int64)
    dst = np.array([b for _, b in pairs], np.int64)
    return src, dst, np.full(len(pairs), float(w["demand"]))


def _flap_phase(t: int, f: Dict) -> str:
    stop = f.get("stop_slot")
    stop = float("inf") if stop is None else stop
    if f["start_slot"] <= t < stop:
        ph = (t - f["start_slot"]) % f["period"]
        down = max(1, int(f["period"] * f["duty"]))
        if ph == 0:
            return "fail"
        if ph == down:
            return "restore"
    elif f.get("stop_slot") is not None and t == f["stop_slot"]:
        return "restore"
    return ""


def point_faults(cfg: Dict, fault_frac, kills) -> List[Dict]:
    """The configuration's faults as one point runs them: a traffic's
    `fault_frac` sets every `link_flap` fault's depth and its `kills`
    every `random_fail` fault's count, where they are not None; a count
    of 0 drops the fault (a pristine fabric)."""
    out = []
    for f in cfg.get("faults", []):
        f = dict(f)
        if f["kind"] == "link_flap" and fault_frac is not None:
            f["frac"] = fault_frac
        if f["kind"] == "random_fail" and kills is not None:
            if kills == 0:
                continue
            f["count"] = kills
        out.append(f)
    return out


def make_events(pt: Point, fab: Fabric) -> Callable[[int], None]:
    """Per-slot fault application.  A `random_fail` fault draws its
    links from `default_rng((workload_seed, 7919, index))`, the index
    counting the point's faults."""
    faults = point_faults(pt.config, pt.fault_frac, pt.kills)

    def planes(f):
        return range(fab.P) if f["plane"] < 0 else (f["plane"],)

    def events(t: int) -> None:
        for i, f in enumerate(faults):
            if f["kind"] == "link_flap":
                ph = _flap_phase(t, f)
                for p in planes(f):
                    if ph == "fail":
                        fab.fail_uplink(p, f["leaf"], f["spine"], f["frac"])
                    elif ph == "restore":
                        fab.restore_uplink(p, f["leaf"], f["spine"])
            elif f["kind"] == "random_fail":
                if t == f["start_slot"]:
                    rng = np.random.default_rng((pt.workload_seed, 7919, i))
                    for p in planes(f):
                        for _ in range(f["count"]):
                            fab.fail_uplink(p, int(rng.integers(fab.L)),
                                            int(rng.integers(fab.S)),
                                            f["frac"])
            else:
                raise ValueError(f"unknown fault kind {f['kind']!r}")
    return events


# ---------------------------------------------------------------------------
# NIC
# ---------------------------------------------------------------------------

def plane_split(mode, rate, eligible, demand):
    F, P = rate.shape
    if mode == "dcqcn":
        w = np.ones((F, P)) / P
        return np.minimum(demand[:, None] * w, rate)
    elig = eligible & (rate > MIN_RATE + 1e-9)
    any_ok = elig.any(1, keepdims=True)
    elig = np.where(any_ok, elig, eligible)
    w = np.where(elig, rate, 0.0)
    s = w.sum(1, keepdims=True)
    w = np.where(s > 0, w / np.maximum(s, 1e-12), 1.0 / P)
    return np.minimum(demand[:, None] * w, np.where(elig, rate, 0.0))


def nic_update(mode, nic, rtt, ecn, probe_ok, q):
    """Per-slot control update of `nic` (a dict of (F, P) arrays)."""
    if mode == "dcqcn":
        ecn_any = ecn.max(1, keepdims=True)
        nic["alpha"] = q((1 - DCQCN_ALPHA_G) * nic["alpha"] +
                         DCQCN_ALPHA_G * (ecn_any > 0))
        cut = nic["rate"] * (1 - nic["alpha"] / 2)
        grow = np.minimum(nic["rate"] + DCQCN_AI, 1.0)
        nic["rate"] = q(np.clip(np.where(ecn_any > 0, cut, grow),
                                MIN_RATE, 1.0))
        return
    if mode != "spx":
        raise ValueError(f"unknown NIC stack {mode!r}")
    rate = nic["rate"]
    rtt_err = (rtt - TARGET_RTT_US) / TARGET_RTT_US
    cut = rate * (SPX_MD + (1 - SPX_MD) * np.clip(1 - ecn, 0, 1))
    trim = rate * (1 - SPX_RTT_GAIN * np.clip(rtt_err, 0, 2))
    grow = np.minimum(rate + SPX_AI, 1.0)
    rate = np.clip(np.where(ecn > 0, cut,
                            np.where(rtt_err > 0.25, trim, grow)),
                   MIN_RATE, 1.0)
    # RTT-probe timeouts exclude a plane (§4.4.1)
    nic["miss"] = np.where(~probe_ok, nic["miss"] + 1, 0)
    dead = nic["miss"] >= PROBE_TIMEOUT
    was = nic["eligible"]
    nic["eligible"] = ~dead
    rate = np.where(nic["eligible"] & ~was, 0.5, rate)
    nic["rate"] = q(np.where(~nic["eligible"], MIN_RATE, rate))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def pair_fractions(fab, q_up, q_down, war):
    """(P, L, L, S) quantized-JSQ softmax spine split per leaf pair."""
    cap = np.minimum(fab.up[:, :, None, :],
                     np.swapaxes(fab.down, 1, 2)[:, None, :, :])
    qq = q_up[:, :, None, :] + np.swapaxes(q_down, 1, 2)[:, None, :, :]
    w = cap.copy()
    if war:
        rw = fab.down / np.maximum(fab.down.max(axis=1, keepdims=True),
                                   1e-9)
        w = w * rw.transpose(0, 2, 1)[:, None, :, :]
    qbin = np.floor(np.clip(qq / 8.0, 0, 1 - 1e-9) * JSQ_BINS) + 1.0
    score = qbin / np.maximum(w, 1e-9)
    logit = np.where(cap > 1e-9, -score / AR_TEMPERATURE, -1e30)
    logit -= logit.max(-1, keepdims=True)
    e = np.exp(logit)
    sums = e.sum(-1, keepdims=True)
    return np.where(sums > 0, e / np.maximum(sums, 1e-30), 0.0)


def rehash_dead(alive, assign, rng, n_spines):
    """ECMP: move assignments whose spine path died onto the first
    alive spine after a seeded offset."""
    cur = np.take_along_axis(alive, assign[:, :, None], axis=2)[:, :, 0]
    bad = ~cur & alive.any(-1)
    if bad.any():
        off = rng.integers(0, n_spines, size=assign.shape)
        order = (off[:, :, None] + np.arange(n_spines)[None, None]) \
            % n_spines
        alive_ord = np.take_along_axis(alive, order, axis=2)
        first = np.argmax(alive_ord, axis=2)
        new = np.take_along_axis(order, first[:, :, None], axis=2)[:, :, 0]
        assign = np.where(bad, new, assign)
    return assign


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def simulate(pt: Point, precision: str = "f64") -> Dict[str, np.ndarray]:
    """Run one point; returns per-flow `mean_goodput`, the per-slot
    `total_goodput` and the last slot's `util_up` (P, L, S)."""
    q = {"f64": _identity, "bf16": bf16}[precision]
    cfg, sim = pt.config, pt.config["sim"]
    fab = Fabric(cfg["topology"])
    src, dst, demand_f = build_flows(cfg, pt.workload_seed)
    src_leaf, dst_leaf = src // fab.hpl, dst // fab.hpl
    F, P, S, L = len(src), fab.P, fab.S, fab.L
    events = make_events(pt, fab)
    rng = np.random.default_rng(pt.sim_seed)
    assign = rng.integers(0, S, size=(F, P))
    nic = {"rate": np.ones((F, P)), "alpha": np.zeros((F, P)),
           "miss": np.zeros((F, P), np.int64),
           "eligible": np.ones((F, P), bool)}
    q_up, q_down = np.zeros_like(fab.up), np.zeros_like(fab.down)
    base_rtt, slot_us = sim["base_rtt_us"], sim["slot_us"]
    same_leaf = src_leaf == dst_leaf
    rec = []
    util = None
    for t in range(pt.slots):
        events(t)
        offered = q(plane_split(pt.nic, nic["rate"], nic["eligible"],
                                demand_f))
        if pt.routing == "ecmp":
            alive = fab.path_capacity(src_leaf, dst_leaf) > 1e-12
            assign = rehash_dead(alive, assign, rng, S)
            frac = np.zeros((F, P, S))
            frac[np.repeat(np.arange(F), P), np.tile(np.arange(P), F),
                 assign.reshape(-1)] = 1.0
        elif pt.routing in ("ar", "war"):
            pair = q(pair_fractions(fab, q_up, q_down, pt.routing == "war"))
            frac = pair[:, src_leaf, dst_leaf, :].transpose(1, 0, 2)
        else:
            raise ValueError(f"unknown routing {pt.routing!r}")

        # link loads -> bottleneck scaling
        fabric_rate = np.where(same_leaf[:, None], 0.0, offered)
        contrib = fabric_rate[:, :, None] * frac              # (F, P, S)
        load_up = np.zeros((L, P, S))
        np.add.at(load_up, src_leaf, contrib)
        load_up = q(load_up.transpose(1, 0, 2))
        load_down = np.zeros((L, P, S))
        np.add.at(load_down, dst_leaf, contrib)
        load_down = q(load_down.transpose(1, 2, 0))
        f_up = q(np.minimum(1.0, fab.up / np.maximum(load_up, EPS)))
        f_down = q(np.minimum(1.0, fab.down / np.maximum(load_down, EPS)))
        fup_g = f_up[:, src_leaf, :].transpose(1, 0, 2)
        fdn_g = f_down.transpose(0, 2, 1)[:, dst_leaf, :].transpose(1, 0, 2)
        through = q((contrib * np.minimum(fup_g, fdn_g)).sum(-1))
        local = np.where(same_leaf[:, None], offered, 0.0)

        # host ports
        acc = fab.access.T                                    # (H, P)
        load_tx = np.zeros((fab.H, P))
        np.add.at(load_tx, src, offered)
        load_rx = np.zeros((fab.H, P))
        np.add.at(load_rx, dst, offered)
        f_tx = np.minimum(1.0, acc / np.maximum(q(load_tx), EPS))
        f_rx = np.minimum(1.0, acc / np.maximum(q(load_rx), EPS))
        alive_acc = (acc[src] > EPS) & (acc[dst] > EPS)
        achieved_pp = (through + local) * np.minimum(f_tx[src], f_rx[dst])
        achieved_pp = q(np.where(alive_acc, achieved_pp, 0.0))

        # rtt / ecn from the queues along each flow's paths
        q_path = (q_up[:, src_leaf, :].transpose(1, 0, 2) +
                  q_down.transpose(0, 2, 1)[:, dst_leaf, :]
                  .transpose(1, 0, 2))
        qmean = np.where(same_leaf[:, None], 0.0, (frac * q_path).sum(-1))
        rtt = q(base_rtt + qmean * slot_us * 0.5)
        ecn = q(np.where(qmean > ECN_QUEUE_THRESH,
                         np.minimum(1.0, qmean / (4 * ECN_QUEUE_THRESH)),
                         0.0))

        # queues
        q_up = np.clip(q_up + (load_up - fab.up) / np.maximum(fab.up, EPS),
                       0.0, Q_CAP)
        q_down = np.clip(q_down + (load_down - fab.down) /
                         np.maximum(fab.down, EPS), 0.0, Q_CAP)
        q_up[fab.up <= EPS] = 0.0
        q_down[fab.down <= EPS] = 0.0
        q_up, q_down = q(q_up), q(q_down)
        util = q(load_up / np.maximum(fab.up, EPS))

        probe_ok = ((fab.access.T[src] > 1e-12) &
                    (fab.access.T[dst] > 1e-12))
        nic_update(pt.nic, nic, rtt, ecn, probe_ok, q)
        # a plane that carries offered traffic but delivers nothing
        # stalls the whole in-order transfer
        stalled = ((offered > 1e-9) & (achieved_pp <= 1e-9)).any(1)
        rec.append(q(np.where(stalled, 0.0, achieved_pp.sum(1))))

    goodput = np.asarray(rec)
    w0 = int(goodput.shape[0] * sim["warmup_frac"])
    mean = goodput[w0:].mean(0) if goodput.shape[0] > w0 \
        else goodput.mean(0)
    return {"mean_goodput": mean, "total_goodput": goodput.sum(1),
            "util_up_last": util}
