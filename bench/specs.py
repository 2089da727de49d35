"""Cells as data: resolve a workload name to its configuration and
traffic files, draw each sweep's grid points from the run's seed, and
hand them to the program as `ScenarioSpec`s built through its public
spec API."""
from __future__ import annotations

import itertools
import json
import os
from typing import Dict, List

import numpy as np

from reference import Point, flow_count, point_faults

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# seed-sequence tags: the window's sweeps and the warm-up never share a
# draw
WINDOW, WARMUP, CHECK = 0, 1, 2


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_mix(config: str, traffic: str, root: str = ROOT):
    """A configuration's data (`bench/configs/<config>.json`) and a
    traffic mix's data (`bench/traffic/<traffic>.json`)."""
    def load(*parts):
        with open(os.path.join(root, "bench", *parts)) as f:
            return json.load(f)
    return load("configs", config + ".json"), \
        load("traffic", traffic + ".json")


def resolve_cell(name: str, root: str = ROOT) -> Dict:
    """Everything one cell runs: its `BENCHMARK.json` entry, the
    configuration and traffic data, the limits of its comparison
    (`bench/limits/<cell>.json`; none until the cell is calibrated) and
    its metrics."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    config, traffic = load_mix(cell["config"], cell["traffic"], root)

    limits_path = os.path.join(root, "bench", "limits", name + ".json")
    limits = {}
    if os.path.exists(limits_path):
        with open(limits_path) as f:
            numbers = json.load(f)["numbers"]
        limits = {k: v["limit"] for k, v in numbers.items()}

    def mine(m):
        return name in m.get("workloads", [name])
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic, "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def sweep_points(config: Dict, traffic: Dict, seed: int, tag: int,
                 index: int) -> List[Point]:
    """The grid points of one sweep: the traffic's axes crossed with
    `seeds_per_sweep` seed pairs drawn from (seed, tag, index)."""
    seeds = [tuple(int(s) for s in np.random.default_rng(
        [seed, tag, index, j]).integers(0, 2 ** 31, size=2))
        for j in range(traffic["seeds_per_sweep"])]
    return [Point(config, r, n, frac, kills, ss, ws)
            for r, n, frac, kills, (ss, ws) in itertools.product(
                traffic["routing"], traffic["nic"], traffic["fault_frac"],
                traffic["kills"], seeds)]


def flow_slots(points: List[Point]) -> int:
    """Simulated flow-slots: real flows times slots, no padding."""
    return sum(flow_count(p.config) * p.slots for p in points)


def scenario_spec(p: Point):
    """The program's input for one point."""
    from repro.scenarios.spec import (FaultSpec, ScenarioSpec, SimSpec,
                                      TenantSpec, TopologySpec,
                                      WorkloadSpec)

    c = p.config
    t, w, s = c["topology"], c["workload"], c["sim"]
    topo = TopologySpec(
        n_leaves=t["n_leaves"], n_spines=t["n_spines"],
        hosts_per_leaf=t["hosts_per_leaf"], n_planes=t["n_planes"],
        parallel_links=t["parallel_links"], link_cap=t["link_cap"],
        access_cap=t["access_cap"], kind=t["kind"])
    ten = c["tenant"]
    tenant = TenantSpec(ten["name"], placement="block",
                        n_hosts=ten.get("n_hosts"), offset=ten["offset"])
    kw = {"sinks": w["sinks"]} if w["kind"] == "incast" else \
        {"fanout": w["fanout"]}
    workload = WorkloadSpec(w["kind"], tenant=ten["name"],
                            demand=w["demand"], **kw)
    faults = tuple(FaultSpec(**{k: v for k, v in f.items()})
                   for f in point_faults(c, p.fault_frac, p.kills))
    sim = SimSpec(slots=s["slots"], slot_us=s["slot_us"],
                  routing=p.routing, nic=p.nic,
                  base_rtt_us=s["base_rtt_us"],
                  warmup_frac=s["warmup_frac"], seed=p.sim_seed,
                  backend="jax")
    return ScenarioSpec(name=c["name"], topo=topo, tenants=(tenant,),
                        workloads=(workload,), faults=faults, sim=sim,
                        workload_seed=p.workload_seed)
