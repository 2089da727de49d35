"""Backend dispatch benchmark: megabatch vs per-group vs NumPy pool.

Runs the paper-style acceptance grid — routing × NIC stack × fault
fraction × seed over one registry scenario — through the three dispatch
paths and reports wall-clock, dispatch/compile counts, warm slots/sec,
and peak RSS:

  * **numpy_pool** — the reference engine over a `ProcessPoolExecutor`
    (one process per point);
  * **per_group**  — the PR 3 JAX path: one compiled program and one
    launch per (scenario, routing, nic, fault) structure, seeds vmapped
    (`jx_dispatch="group"`);
  * **megabatch**  — the fused path: the whole grid stacks into ONE
    `jit(vmap)`/pmap launch that compiles once, with per-element traced
    routing/NIC branch selection (`jx_dispatch="megabatch"`).

Each JAX path is timed cold (first call pays XLA compilation) and warm
(executable cache hit — the steady state of any repeated sweep).  The
machine-readable summary is written to `BENCH_backend.json` so CI can
assert the single-launch property (`megabatch.dispatches == 1`,
`megabatch.compiles == 1`) and track the perf trajectory as an
artifact.

CLI (CI runs the smoke variant; its timings are XLA-CPU numbers):

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.backend_bench
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.backend_bench --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time
from typing import Optional, Tuple

# on the CPU platform (JAX_PLATFORMS=cpu), one XLA host device per core,
# so the jax backend's batches shard across cores like the NumPy pool's
# workers do; must be set before JAX initializes (the runner imports it
# lazily, on first use).  On an accelerator the flag would only split
# the host, so it stays unset there.
_flags = os.environ.get("XLA_FLAGS", "")
if (os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
        and "xla_force_host_platform_device_count" not in _flags):
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count="
        f"{os.cpu_count() or 1}").strip()

from repro.experiments import (Axis, Experiment, execute_points,  # noqa: E402
                               product)
from repro.scenarios import list_scenarios  # noqa: E402

from .common import emit

DEFAULT_SCENARIO = "flap_during_incast"
# the giga-scale single point (4096 hosts / 102,400 flows): the shape
# that forces the engine's sparse segment-summed aggregation path
LARGE_SCENARIO = "giga_fabric_storage"
DEFAULT_JSON = "BENCH_backend.json"
# the committed perf trajectory: the last blessed run of this benchmark,
# checked in at the repo root and regenerated whenever perf moves on
# purpose (CI's megabatch-smoke gate fails a >20% warm-throughput drop)
BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_backend.json")
SCHEMA = 1


def bench_grid(scenario: str, routings, nics, fracs, n_seeds: int,
               slots: Optional[int]) -> Experiment:
    """The acceptance grid: routing × nic × fault-frac × seed (the
    fault-frac axis rescales the scenario's first fault in place;
    fault-less scenarios drop that axis rather than crash)."""
    from repro.scenarios import get_scenario

    axes = [Axis("sim.routing", tuple(routings)),
            Axis("sim.nic", tuple(nics))]
    if fracs and get_scenario(scenario).faults:
        axes.append(Axis("faults[0].frac", tuple(fracs)))
    axes.append(Axis("seed", tuple(range(n_seeds))))
    if slots:
        axes.append(Axis("sim.slots", (slots,)))
    return Experiment(name="backend_bench.grid", base=scenario,
                      axes=product(*axes))


def compare_baseline(out: dict, base: Optional[dict]) -> dict:
    """Fresh run vs the committed snapshot.  Only megabatch warm
    throughput is gated — cold time is dominated by XLA compile noise.
    Runs are comparable only on the same grid and device count; a
    mismatch reports `comparable: false` so CI skips instead of failing
    on cross-machine variance."""
    if base is None:
        return {"comparable": False, "reason": "no committed baseline"}
    if base.get("schema") != out["schema"]:
        return {"comparable": False,
                "reason": f"schema {base.get('schema')} != {out['schema']}"}
    if base.get("grid") != out["grid"]:
        return {"comparable": False, "reason": "grid differs"}
    if base.get("devices") != out["devices"]:
        return {"comparable": False,
                "reason": (f"devices {base.get('devices')} != "
                           f"{out['devices']}")}
    ref = base.get("megabatch", {}).get("warm_slots_per_s")
    if not ref:
        return {"comparable": False,
                "reason": "baseline has no megabatch.warm_slots_per_s"}
    cur = out["megabatch"]["warm_slots_per_s"]
    cmp = {"comparable": True, "reason": "",
           "baseline_warm_slots_per_s": ref,
           "warm_slots_per_s": cur, "ratio": cur / ref}
    # the giga-scale point's trajectory, when both the run and the
    # snapshot carry one for the same scenario/shape: warm-throughput
    # ratio plus the wall-clock ratio CI gates (>= 0.8 — a fresh run
    # may be at most 25% slower than the committed snapshot)
    lb, lo = base.get("large_scale"), out.get("large_scale")
    if lb and lo and lb.get("warm_slots_per_s") and (
            {k: lo.get(k) for k in ("scenario", "hosts", "flows",
                                    "slots", "x64")}
            == {k: lb.get(k) for k in ("scenario", "hosts", "flows",
                                       "slots", "x64")}):
        cmp["large_ratio"] = (lo["warm_slots_per_s"]
                              / lb["warm_slots_per_s"])
        if lb.get("wall_s") and lo.get("wall_s"):
            cmp["large_wall_ratio"] = lb["wall_s"] / lo["wall_s"]
    return cmp


def run_large(scenario: str = LARGE_SCENARIO,
              slots: Optional[int] = None, warm_iters: int = 2) -> dict:
    """Time the giga-scale single point (O(4k) hosts / O(100k) flows)
    through the megabatch path — cold (XLA compile) and warm.  At this
    shape `agg_mode_default` selects the sparse segment-summed
    aggregation, so this is the perf point that guards the kernelized
    hot path at scale."""
    import jax

    from repro.netsim.jx import dispatch_stats, reset_dispatch_stats
    from repro.netsim.jx.engine import agg_mode_default
    from repro.scenarios import get_scenario
    from repro.scenarios.compile import compile_scenario

    spec = get_scenario(scenario)
    if slots:
        spec = spec.with_sim(slots=slots)
    compiled = compile_scenario(spec)
    n_flows = len(compiled.flows)
    topo = spec.topo
    reset_dispatch_stats()
    t_all = time.perf_counter()
    execute_points([spec], backend="jax", jx_dispatch="megabatch")
    cold = time.perf_counter() - t_all
    stats = dispatch_stats()
    warm = _time_best(
        lambda: execute_points([spec], backend="jax",
                               jx_dispatch="megabatch"), iters=warm_iters)
    row = {"scenario": scenario, "hosts": topo.n_hosts,
           "flows": n_flows, "planes": topo.n_planes,
           "slots": spec.sim.slots,
           "x64": bool(jax.config.jax_enable_x64),
           "agg_mode": agg_mode_default(topo.n_hosts, topo.n_leaves,
                                        topo.n_paths, topo.n_planes),
           "cold_s": cold, "warm_s": warm,
           "wall_s": time.perf_counter() - t_all,
           "peak_rss_mb": peak_rss_mb(),
           "dispatches": stats["dispatches"],
           "compiles": stats["compiles"],
           "warm_slots_per_s": spec.sim.slots / max(warm, 1e-9)}
    emit(f"backend_bench.large.{scenario}", warm * 1e6,
         f"hosts={topo.n_hosts},flows={n_flows},cold_s={cold:.2f},"
         f"warm_s={warm:.2f},agg={row['agg_mode']},"
         f"slots_per_s={row['warm_slots_per_s']:.1f},"
         f"rss_mb={row['peak_rss_mb']:.0f}")
    return row


def peak_rss_mb() -> float:
    """Peak resident set of this process in MiB (`ru_maxrss` is KiB on
    Linux but bytes on macOS)."""
    unit = 1 if sys.platform == "darwin" else 1024
    return (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * unit
            / 2**20)


def _time_best(fn, iters: int) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(scenario: str = DEFAULT_SCENARIO,
        routings: Tuple[str, ...] = ("ar", "war", "ecmp"),
        nics: Tuple[str, ...] = ("spx", "dcqcn", "global", "esr", "swlb"),
        fracs: Tuple[float, ...] = (0.2, 0.4, 0.6, 0.8),
        n_seeds: int = 2, slots: Optional[int] = None,
        processes: Optional[int] = None, with_numpy: bool = True,
        json_out: Optional[str] = DEFAULT_JSON,
        baseline: Optional[str] = BASELINE_PATH,
        large: bool = False,
        large_slots: Optional[int] = None) -> dict:
    from repro.netsim.jx import dispatch_stats, reset_dispatch_stats

    # read the committed snapshot up front — json_out may legitimately
    # point at the same file (CI regenerates the baseline in place)
    base = None
    if baseline and os.path.exists(baseline):
        with open(baseline, encoding="utf-8") as f:
            base = json.load(f)

    exp = bench_grid(scenario, routings, nics, fracs, n_seeds, slots)
    points = [p.spec for p in exp.points()]
    n_points = len(points)
    spec_slots = points[0].sim.slots
    total_slots = n_points * spec_slots
    swept_fracs = "faults[0].frac" in exp.coord_names()
    grid_desc = {"scenario": scenario, "routings": list(routings),
                 "nics": list(nics),
                 "fault_fracs": list(fracs) if swept_fracs else [],
                 "seeds": n_seeds, "slots": spec_slots,
                 "points": n_points}

    out = {"schema": SCHEMA, "grid": grid_desc,
           "devices": int(os.cpu_count() or 1)}

    # numpy first: the process pool must fork before JAX spins up its
    # thread pools in this process
    rows = {}
    if with_numpy:
        t0 = time.perf_counter()
        rows["numpy"] = execute_points(points, processes=processes,
                                       backend="numpy")
        t_np = time.perf_counter() - t0
        out["numpy_pool"] = {"warm_s": t_np,
                             "slots_per_s": total_slots / max(t_np, 1e-9)}
        emit(f"backend_bench.{scenario}.numpy_pool", t_np * 1e6,
             f"wall_s={t_np:.3f},points={n_points}")

    for mode in ("group", "megabatch"):
        reset_dispatch_stats()
        t0 = time.perf_counter()
        rows[mode] = execute_points(points, backend="jax",
                                    jx_dispatch=mode)
        cold = time.perf_counter() - t0
        cold_stats = dispatch_stats()
        reset_dispatch_stats()
        warm = _time_best(
            lambda m=mode: execute_points(points, backend="jax",
                                          jx_dispatch=m), iters=3)
        warm_stats = dispatch_stats()
        key = "per_group" if mode == "group" else "megabatch"
        out[key] = {
            "cold_s": cold, "warm_s": warm,
            "compile_s": max(0.0, cold - warm),
            "dispatches": cold_stats["dispatches"],
            "compiles": cold_stats["compiles"],
            "warm_compiles": warm_stats["compiles"],
            "warm_slots_per_s": total_slots / max(warm, 1e-9),
        }
        emit(f"backend_bench.{scenario}.{key}", warm * 1e6,
             f"cold_s={cold:.3f},warm_s={warm:.3f},"
             f"dispatches={cold_stats['dispatches']},"
             f"compiles={cold_stats['compiles']},"
             f"slots_per_s={total_slots / max(warm, 1e-9):.0f}")

    # dispatch-path agreement (float32 jitter tolerated via the 4dp CSV
    # rounding; exact 1e-5 x64 row-identity is tests/test_megabatch.py's
    # job)
    mism = sum(a.to_row() != b.to_row()
               for a, b in zip(rows["group"], rows["megabatch"]))
    out["row_mismatches_group_vs_megabatch"] = int(mism)
    out["speedup_warm_vs_per_group"] = (
        out["per_group"]["warm_s"] / max(out["megabatch"]["warm_s"],
                                         1e-9))
    if with_numpy:
        out["speedup_warm_vs_numpy"] = (
            out["numpy_pool"]["warm_s"] / max(out["megabatch"]["warm_s"],
                                              1e-9))
    out["peak_rss_bytes"] = int(peak_rss_mb() * 2**20)
    emit(f"backend_bench.{scenario}.speedup", 0.0,
         f"megabatch_vs_per_group={out['speedup_warm_vs_per_group']:.2f}x"
         + (f",megabatch_vs_numpy={out['speedup_warm_vs_numpy']:.2f}x"
            if with_numpy else "")
         + f",row_mismatches={mism}")

    if large:
        out["large_scale"] = run_large(slots=large_slots)

    out["baseline"] = cmp = compare_baseline(out, base)
    if cmp["comparable"]:
        print(f"# bench baseline: ratio={cmp['ratio']:.3f} "
              f"(warm {cmp['warm_slots_per_s']:.0f} vs committed "
              f"{cmp['baseline_warm_slots_per_s']:.0f} slots/s)"
              + (f", large_ratio={cmp['large_ratio']:.3f}"
                 if "large_ratio" in cmp else ""),
              flush=True)
    else:
        print(f"# bench baseline: not comparable ({cmp['reason']})",
              flush=True)

    if json_out:
        with open(json_out, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=2)
        print(f"# bench json: {json_out}", flush=True)
    return out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scenario", default=DEFAULT_SCENARIO,
                   choices=list_scenarios())
    p.add_argument("--seeds", type=int, default=None,
                   help="seed-axis length (default 2)")
    p.add_argument("--routings", nargs="+", default=None,
                   help="default: ar war ecmp")
    p.add_argument("--nics", nargs="+", default=None,
                   help="default: all five stacks (smoke: spx dcqcn)")
    p.add_argument("--fracs", nargs="+", type=float, default=None,
                   help="fault-frac axis values (default .2 .4 .6 .8; "
                        "smoke: .3 .5 .8)")
    p.add_argument("--slots", type=int, default=None,
                   help="override spec slots (default: spec's own; "
                        "smoke: 120)")
    p.add_argument("--processes", type=int, default=None,
                   help="numpy pool size (default: min(points, cpus))")
    p.add_argument("--no-numpy", action="store_true",
                   help="skip the process-pool baseline")
    p.add_argument("--json-out", default=DEFAULT_JSON)
    p.add_argument("--baseline", default=BASELINE_PATH,
                   help="committed snapshot to compare against "
                        "(default: repo-root BENCH_backend.json; "
                        "'' disables)")
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized defaults: 2 nics x 3 fracs x 2 "
                        "seeds, 120 slots (36 points); explicit flags "
                        "still win")
    p.add_argument("--large", action="store_true",
                   help="also time the giga-scale single point "
                        f"({LARGE_SCENARIO}: 4096 hosts, 102,400 "
                        "flows) through the sparse aggregation path")
    p.add_argument("--large-slots", type=int, default=None,
                   help="override the giga point's slot count")
    args = p.parse_args(argv)
    print("name,us_per_call,derived")
    # smoke only changes the *defaults* — explicit flags always win
    if args.smoke:
        nics, fracs, slots = ("spx", "dcqcn"), (0.3, 0.5, 0.8), 120
    else:
        nics = ("spx", "dcqcn", "global", "esr", "swlb")
        fracs, slots = (0.2, 0.4, 0.6, 0.8), None
    run(args.scenario,
        routings=tuple(args.routings or ("ar", "war", "ecmp")),
        nics=tuple(args.nics) if args.nics else nics,
        fracs=tuple(args.fracs) if args.fracs is not None else fracs,
        n_seeds=args.seeds if args.seeds is not None else 2,
        slots=args.slots if args.slots is not None else slots,
        processes=args.processes, with_numpy=not args.no_numpy,
        json_out=args.json_out, baseline=args.baseline or None,
        large=args.large, large_slots=args.large_slots)


if __name__ == "__main__":
    main(sys.argv[1:])
