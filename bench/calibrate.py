#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from.

    python3 bench/calibrate.py --workload <cell> [--seeds 12] [--control-seeds 3]

For each seed, one sweep of the cell (the sweep a run of that seed would
compare) goes through the timed path on the chip and is compared with
the float64 reference (`compare.summary` over the sweep's points): that
is the program's reading.  For the first `--control-seeds` seeds the
control, the reference computed in bfloat16 (`reference.simulate(...,
precision="bf16")`), is compared with the float64 reference the same
way.  The lower reading of a number is the largest the program gives,
the upper the smallest the control gives.  One JSON object per line;
the last line holds both readings.
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]


def _control(point):
    from reference import simulate

    return simulate(point, precision="bf16")


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--base-seed", type=int, default=3_000_000_000)
    args = p.parse_args(argv)

    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    import numpy as np

    import run
    from compare import NUMBERS, branch, summary
    from reference import simulate
    from specs import CHECK, WINDOW, resolve_cell, sweep_points

    cell = resolve_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        print(f"calibrate: needs {cell['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)
    from repro.experiments import enable_compile_cache

    enable_compile_cache(run.CACHE_DIR)
    seeds = [args.base_seed + 7919 * i for i in range(args.seeds)]
    run.set_up(cell, seeds[0], 1.0)
    swept = []
    for s in seeds:
        index = int(np.random.default_rng([s, CHECK]).integers(0, 3))
        pts = sweep_points(cell["config"], cell["traffic"], s, WINDOW,
                           index)
        rows, _ = run.sweep(pts)
        swept.append((s, pts, [m.extra["raw"] for m in rows]))
    t = time.perf_counter()
    n = max(1, min(16, (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=n, mp_context=ctx) as ex:
        refs = [list(ex.map(simulate, pts)) for _, pts, _ in swept]
        ctrl = [list(ex.map(_control, pts))
                for _, pts, _ in swept[:args.control_seeds]]
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": [], "control": [],
           "reference_s": time.perf_counter() - t}
    for (s, pts, got), ref in zip(swept, refs):
        row = dict(seed=s, **summary(ref, got, [branch(p) for p in pts]))
        out["program"].append(row)
        print(json.dumps({"program": row}), flush=True)
    for (s, pts, _), c, ref in zip(swept, ctrl, refs):
        row = dict(seed=s, **summary(ref, c, [branch(p) for p in pts]))
        out["control"].append(row)
        print(json.dumps({"control": row}), flush=True)
    out["lower"] = {k: max(r[k] for r in out["program"]) for k in NUMBERS}
    out["upper"] = {k: min(r[k] for r in out["control"]) for k in NUMBERS}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
