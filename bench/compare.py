"""The comparison that decides `correct`: how far the rows the program
produced in the window stray from the float64 reference.

float32 trajectories fork from the float64 reference where a queue sits
on an ECN threshold: a forked point keeps its averages roughly but every
flow of it moves by up to a few 1e-3 of line rate, about as far as a
lower precision moves it.  So the worst point says little, and what
separates a sound float32 run from a less precise one is how the bulk
of the answers track: the median flow, the median slot, the median
link, and the share of points that track at all.  Each cell holds the
numbers that separate (`bench/limits/<cell>.json`); `calibrate.py`
reports the others beside them.

A grid mixes branches (routing x NIC stack) that a fault can hit alone,
and a median shrugs off anything that touches fewer than half of its
samples.  So every number is taken over each branch's points apart,
and the largest of the branches is the one held.

The worst-case numbers are the program's own smoke-test comparison
(`chip_smoke.divergence` / `worst`), copied here so that the yardstick
stays fixed.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Sequence, Tuple

import numpy as np

# a flow "forked" when its mean goodput moved more than this from the
# reference (line-rate units); a point "tracks" the reference when no
# flow moved more than TRACK_TOL
FORK_TOL = 1e-3
TRACK_TOL = 1e-5

NUMBERS = (
    "untracked_share",     # share of points with a flow off by > TRACK_TOL
    "p50_flow_abs",        # median |d mean goodput| over the flows
    "p99_flow_abs",        # 99th percentile of the same
    "p50_slot_rel",        # median |d total goodput| over the slots,
                           # relative to the point's mean total
    "p50_link_abs",        # median |d utilization| over the uplinks
                           # that carry load in the reference
    "forked_frac",         # share of flows off by > FORK_TOL
    "max_abs_goodput",     # worst flow
    "mean_goodput_diff",   # worst point's |d mean over flows|
    "total_goodput_rel",   # worst point's |d time-mean total| (relative)
)


def _point(ref: Dict, got: Dict):
    """Per-flow, per-slot and per-link deviations of one point, or None
    when the row has the wrong shape."""
    if any(np.shape(got[k]) != np.shape(ref[k])
           for k in ("mean_goodput", "total_goodput", "util_up_last")):
        return None
    total_r = max(float(np.mean(ref["total_goodput"])), 1e-12)
    return (np.abs(got["mean_goodput"] - ref["mean_goodput"]),
            np.abs(got["total_goodput"] - ref["total_goodput"]) / total_r,
            np.abs(got["util_up_last"] - ref["util_up_last"])[
                ref["util_up_last"] > 0],
            abs(float(got["mean_goodput"].mean()
                      - ref["mean_goodput"].mean())),
            abs(float(np.mean(got["total_goodput"]))
                - float(np.mean(ref["total_goodput"]))) / total_r)


def _numbers(pts) -> Dict[str, float]:
    flow = np.concatenate([p[0] for p in pts])
    slot = np.concatenate([p[1] for p in pts])
    link = np.concatenate([p[2] for p in pts] + [np.zeros(1)])
    return {
        "untracked_share": float(np.mean([p[0].max() > TRACK_TOL
                                          for p in pts])),
        "p50_flow_abs": float(np.median(flow)),
        "p99_flow_abs": float(np.quantile(flow, 0.99)),
        "p50_slot_rel": float(np.median(slot)),
        "p50_link_abs": float(np.median(link)),
        "forked_frac": float(np.mean(flow > FORK_TOL)),
        "max_abs_goodput": float(flow.max()),
        "mean_goodput_diff": max(p[3] for p in pts),
        "total_goodput_rel": max(p[4] for p in pts),
    }


def summary(refs: List[Dict], gots: List[Dict],
            branches: Sequence[Hashable]) -> Dict[str, float]:
    """Every number of `NUMBERS`, each the largest over the branches;
    `branches` names each compared point's branch."""
    pts = [_point(r, g) for r, g in zip(refs, gots)]
    if (not pts or any(p is None for p in pts)
            or not len(refs) == len(gots) == len(branches)):
        return {k: float("inf") for k in NUMBERS}
    per = [_numbers([p for p, b in zip(pts, branches) if b == key])
           for key in dict.fromkeys(branches)]
    return {k: max(n[k] for n in per) for k in NUMBERS}


def branch(point) -> Tuple[str, str]:
    """The branch of a grid point: its routing and NIC stack."""
    return point.routing, point.nic


def checks(numbers: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict]:
    """The held numbers, each beside its limit.  With no limit at all
    the configuration is not calibrated, and nothing passes."""
    if not limits:
        return {k: {"value": v, "limit": None, "ok": False}
                for k, v in numbers.items()}
    return {k: {"value": numbers[k], "limit": lim,
                "ok": numbers[k] <= lim} for k, lim in limits.items()}
