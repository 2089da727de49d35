"""Fig 14 — large-scale resiliency (the paper's NSX compositional method).

(a) Fabric flaps, 64K single-plane 2-level FT: P99 CCT of 256-rank ring
collectives vs concurrent failed links k, expectation-weighted by the
Poisson pmf of concurrent failures (10 flaps/min fleet, 10 s duration).
The k sweep is the `fig14a_fabric_flaps` experiment — a `faults` axis of
exact-k random uplink kills, averaged over a seed axis.
(b) 256K multi-plane endpoint flaps: P99 CCT slowdown as a function of the
NIC's plane-failover convergence time (pristine/failed/degraded NIC-state
composition) — pure composition math, no fabric sim.

`--giga` adds (c): a *directly simulated* 4096-host / 102,400-flow
multiplane point (`giga_fabric_storage`) through the JAX engine's sparse
segment-summed aggregation path — the pristine fabric vs the same fabric
with 8 concurrent random link kills, fig14a's degradation question asked
of the full fluid simulation instead of the compositional proxy.

`--giga --full` widens (c) into the sweep the compositional method
approximates: k ∈ {0, 2, 4, 8} concurrent kills × a seed axis, all 12
giga-shape points fused by the streaming megabatch path into one
dispatch (and one compile) per shape bucket — the pristine timeline and
the faulted one — with host prep pipelined against device execution."""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core.fault_tolerance import concurrent_failure_pmf
from repro.experiments import execute_points, get_experiment, run_experiment

from .common import emit


def run() -> None:
    # ---- (a) fabric flaps: expectation over the k-failure pmf ----
    pmf = concurrent_failure_pmf(flaps_per_minute=10, duration_s=10,
                                 max_k=10)
    rs = run_experiment(get_experiment("fig14a_fabric_flaps"))
    # p99 CCT per k, seed-averaged (slowest flow gates the collective)
    mean_cct = {key[0]: float(np.mean([r["extra"]["p99_cct"]
                                       for r in grp.rows()]))
                for key, grp in rs.group_by("axis.faults").items()}
    ks = sorted(mean_cct)
    cct_k = [mean_cct[k] for k in ks]
    cct0 = cct_k[0]
    expected = float(np.dot(pmf, cct_k))
    emit("fig14a.fabric_flaps.p99cct", 0.0,
         f"normalized={expected / cct0:.4f},worst_k10="
         f"{cct_k[-1] / cct0:.3f}")

    # ---- (b) endpoint flaps: paper's NIC-state composition ----
    # states: pristine (bw 1.0), failed (bw 0 until converged), degraded
    # (0.75 of line after convergence). One failure per 256-rank ring.
    flap_rate_per_s = 10.0 / 60.0
    duration_s = 10.0
    n_collectives, iters = 1024, 200
    cct_base = 1.0
    for conv_ms in (1, 10, 30, 100, 300):
        conv_s = conv_ms / 1000.0
        rng2 = np.random.default_rng(13)
        p99s = []
        for _ in range(iters):
            # fraction of collectives touched by >=1 flapped NIC this iter
            lam = flap_rate_per_s * (duration_s + conv_s)
            n_fail = rng2.poisson(lam * 16)       # fleet-scaled proxy
            ccts = np.full(n_collectives, cct_base)
            hit = rng2.choice(n_collectives, size=min(n_fail,
                                                      n_collectives),
                              replace=False)
            # during convergence the ring stalls; after, it runs at 0.75
            frac_stalled = conv_s / (conv_s + duration_s)
            cct_hit = frac_stalled * 60.0 + (1 - frac_stalled) / 0.75
            ccts[hit] = cct_hit
            p99s.append(np.quantile(ccts, 0.99))
        slow = float(np.mean(p99s))
        emit(f"fig14b.endpoint_flap.conv{conv_ms}ms", conv_ms * 1e3,
             f"p99cct_slowdown={slow:.2f}x")


def run_giga(slots: int = 0) -> None:
    """(c) the giga-scale point, simulated rather than composed: mean
    goodput of 102,400 storage flows over 4096 hosts with and without
    8 concurrent random fabric link kills, plus the wall clock the
    sparse aggregation path takes for each."""
    from dataclasses import replace

    from repro.scenarios import get_scenario

    spec = get_scenario("giga_fabric_storage")
    if slots:
        spec = spec.with_sim(slots=slots)
    pristine = replace(spec, faults=())
    t0 = time.perf_counter()
    out = execute_points([pristine, spec], backend="jax")
    wall = time.perf_counter() - t0
    g0, gk = out[0].mean_goodput, out[1].mean_goodput
    emit("fig14c.giga_sim.k8_random_kill", wall * 1e6,
         f"hosts=4096,flows=102400,goodput_pristine={g0:.4f},"
         f"goodput_k8={gk:.4f},degradation={gk / g0:.4f},"
         f"wall_s={wall:.1f}")


def run_giga_full(slots: int = 0, seeds=(0, 1, 2),
                  ks=(0, 2, 4, 8)) -> dict:
    """(c) at full sweep width: fig14a's k-concurrent-failure question
    asked of the directly simulated giga point.  k ∈ {0, 2, 4, 8}
    random fabric link kills × a fault/ECMP seed axis, every point at
    4096 hosts / 102,400 flows, fused by the megabatch path into one
    dispatch per shape bucket (the pristine timeline and the faulted
    one) with host prep pipelined against device execution.  Returns
    the summary dict it emits, so the CI smoke can assert on it."""
    from dataclasses import replace

    from repro.scenarios import get_scenario

    spec = get_scenario("giga_fabric_storage")
    if slots:
        spec = spec.with_sim(slots=slots)
    points = []
    for k in ks:
        for s in seeds:
            p = spec.with_sim(seed=s)
            # `random_fail` count=0 means "fail each link independently
            # with probability frac", not "zero concurrent failures" —
            # the pristine point drops the fault instead
            points.append(replace(
                p, faults=() if k == 0 else
                (replace(spec.faults[0], count=k),)))
    flight = {}
    t0 = time.perf_counter()
    out = execute_points(points, backend="jax",
                         flight=flight)
    wall = time.perf_counter() - t0
    by_k = {}
    for p, m in zip(points, out):
        k = p.faults[0].count if p.faults else 0
        by_k.setdefault(k, []).append(m.mean_goodput)
    g0 = float(np.mean(by_k[ks[0]]))
    for k in ks:
        gk = float(np.mean(by_k[k]))
        emit(f"fig14c.giga_full.k{k}", wall * 1e6 / len(points),
             f"goodput={gk:.4f},degradation={gk / g0:.4f},"
             f"seeds={len(seeds)}")
    stats = flight.get("dispatch_stats", {})
    pipe = flight.get("pipeline", {})
    summary = {"points": len(points), "wall_s": wall,
               "dispatches": stats.get("dispatches"),
               "compiles": stats.get("compiles"),
               "launches": pipe.get("launches"),
               "pipelined": bool(pipe.get("pipelined")),
               "degradation": {k: float(np.mean(by_k[k]) / g0)
                               for k in ks}}
    emit("fig14c.giga_full.sweep", wall * 1e6,
         f"points={len(points)},wall_s={wall:.1f},"
         f"dispatches={summary['dispatches']},"
         f"compiles={summary['compiles']},"
         f"pipelined={summary['pipelined']}")
    return summary


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--giga", action="store_true",
                   help="also simulate the 4096-host / 102,400-flow "
                        "point directly (JAX sparse aggregation path)")
    p.add_argument("--giga-only", action="store_true",
                   help="skip (a)/(b); just the giga sim point")
    p.add_argument("--giga-slots", type=int, default=0,
                   help="override the giga point's slot count")
    p.add_argument("--full", action="store_true",
                   help="with --giga: the full k x seed sweep (k in "
                        "{0,2,4,8} x 3 seeds), one pipelined megabatch "
                        "dispatch per shape bucket")
    args = p.parse_args(argv)
    if not args.giga_only:
        run()
    if args.giga or args.giga_only:
        if args.full:
            run_giga_full(slots=args.giga_slots)
        else:
            run_giga(slots=args.giga_slots)


if __name__ == "__main__":
    main(sys.argv[1:])
