"""Bytes each Pallas kernel of the slot step must read and write, from
the shapes of one grid point.

The count is the algorithm's: the operands and results a stage needs
in their natural shapes (float32 values, boolean masks as one byte),
for the point's real flows and its own routing and NIC branch.  Flow
padding, lane padding and the NIC branches a batched `lax.switch`
evaluates besides the point's own are not counted, so a share of the
roofline stays at or under what the chip could do for the same work.

Shapes: F flows, P planes, L leaves, S spines, H hosts.
"""
from __future__ import annotations

from typing import Dict, Iterable

from reference import flow_count

F32, BOOL = 4, 1


def per_slot(point, dense_ecmp: bool) -> Dict[str, int]:
    """Bytes per slot of one point, per kernel name.  `dense_ecmp`:
    whether ECMP link loads go through the fused bucket-sum kernel (the
    dense aggregation path) rather than an XLA scatter plus the
    elementwise bottleneck kernel."""
    t = point.config["topology"]
    F = flow_count(point.config)
    P, L, S = t["n_planes"], t["n_leaves"], t["n_spines"]
    H = L * t["hosts_per_leaf"]
    PLS = P * L * S
    out = {
        # rate, eligibility and demand in; offered per plane out
        "_plane_split_kernel": F32 * F * P + BOOL * F * P + F32 * F
        + F32 * F * P,
        # host ports, transmit and receive: capacity and load in,
        # scale out
        "_bottleneck_kernel": 2 * 3 * F32 * H * P,
        # uplinks: queue, load, capacity in, queue and utilization out;
        # downlinks: the same without the utilization
        "_queue_update_kernel": 5 * F32 * PLS + 4 * F32 * PLS,
    }
    if point.routing == "ecmp" and dense_ecmp:
        # each flow's plane rate into its uplink and downlink bucket,
        # capacities in, loads and scales out
        out["_load_bottleneck_kernel"] = 2 * F32 * F * P \
            + 2 * 3 * F32 * PLS
    else:
        # uplink and downlink scales from capacity and load
        out["_bottleneck_kernel"] += 2 * 3 * F32 * PLS
    if point.routing in ("ar", "war"):
        # queues and capacities of both directions in, the
        # (P, L, L, S) spine split out
        out["_pair_score_kernel"] = 4 * F32 * PLS + F32 * P * L * L * S
    if point.nic == "dcqcn":
        # mean queue, rate, alpha in; rtt, ecn, rate, alpha out
        out["_nic_update_kernel"] = 7 * F32 * F * P
    else:
        # mean queue, rate in; rtt, ecn, rate out
        out["_nic_update_kernel"] = 5 * F32 * F * P
    return out


def sweep_bytes(points: Iterable, dense_ecmp: bool) -> Dict[str, int]:
    """Bytes per kernel over whole runs of `points`."""
    total: Dict[str, int] = {}
    for p in points:
        for k, b in per_slot(p, dense_ecmp).items():
            total[k] = total.get(k, 0) + b * p.slots
    return total
