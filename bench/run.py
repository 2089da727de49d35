#!/usr/bin/env python3
"""Benchmark of the megabatch simulator on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration
(`bench/configs/<config>.json`, a deployment written out as data) and a
traffic mix (`bench/traffic/<traffic>.json`: grid axes and seeds per
sweep).  One sweep is one `execute_points(points, backend="jax")` call
over the traffic's grid, with fresh seeds drawn from `--seed` and the
sweep's index.

Set-up: start JAX on the TPU, then warm up every program shape the
window will use with sweeps on seeds the window does not draw.  The
window then runs whole sweeps back to back until `--seconds` have
passed.  `--trace 0` reports the cell's end-to-end metrics; `--trace 1`
profiles a window of at most `TRACE_SECONDS` and reports the per-layer
metrics, each read by `bench/metrics/<name>.py`.

After the window one sweep of it, drawn from the seed, is compared point
by point with the float64 NumPy reference (`reference.py`), each number
against its limit in `bench/limits/<cell>.json` (`compare.py`).  The
last line of standard output is the result as JSON.  Without a TPU, or
with another number of chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

from specs import (CHECK, WARMUP, WINDOW, flow_slots,  # noqa: E402
                   resolve_cell, scenario_spec, sweep_points)

TRACE_SECONDS = 8.0
MIN_TRACED_SWEEPS = 2
# JAX's persistent compilation cache: a fixed directory of the checkout
# that only the benchmark writes, so every run after a checkout's first
# loads its programs instead of compiling them
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
RAW_FIELDS = ("mean_goodput", "total_goodput", "util_up_last")


def _raw(spec, compiled, result):
    """`derive` hook: keep the per-flow and per-link arrays of a point
    for the comparison."""
    import numpy as np

    return {"raw": {f: np.array(getattr(result, f)) for f in RAW_FIELDS}}


def sweep(points):
    """One timed call of the program over a sweep's grid points."""
    from repro.experiments import execute_points

    flight: dict = {}
    rows = execute_points([scenario_spec(p) for p in points],
                          backend="jax", derive=_raw, flight=flight)
    return rows, flight


def signature(points):
    """The programs (and their operand shapes) a sweep would launch."""
    import jax

    from repro.netsim.jx.megabatch import megabatch_programs
    from repro.scenarios import compile_scenario

    progs = megabatch_programs([compile_scenario(scenario_spec(p))
                                for p in points])
    return tuple((id(fn), tuple((a.shape, str(a.dtype))
                                for a in jax.tree.leaves(args)))
                 for fn, args in progs)


def set_up(cell, seed, seconds):
    """Warm up every program shape the window will use.  Returns the
    number of warm-up sweeps."""
    config, traffic = cell["config"], cell["traffic"]
    first = sweep_points(config, traffic, seed, WARMUP, 0)
    sweep(first)
    n = 1
    if not traffic.get("warm_every_shape"):
        return n
    # program shapes that depend on the seeds (bucketed aggregation
    # widths): probe the sweeps the window can reach, and warm up each
    # shape the warm-up sweep did not
    second = sweep_points(config, traffic, seed, WARMUP, 1)
    t = time.perf_counter()
    sweep(second)
    per_sweep = time.perf_counter() - t
    n += 1
    seen = {signature(first), signature(second)}
    for i in range(math.ceil(1.5 * seconds / per_sweep) + 2):
        pts = sweep_points(config, traffic, seed, WINDOW, i)
        sig = signature(pts)
        if sig not in seen:
            seen.add(sig)
            sweep(pts)
            n += 1
    return n


def run_window(cell, seed, seconds, trace):
    """Whole sweeps until `seconds` have passed.  Returns the window's
    counts and the rows of the sweep drawn for the comparison."""
    import jax
    import numpy as np

    from spans import SWEEP

    config, traffic = cell["config"], cell["traffic"]
    check_index = int(np.random.default_rng([seed, CHECK]).integers(0, 3))
    out = {"sweeps": 0, "flow_slots": 0, "points": 0, "compiles": 0,
           "launches": 0, "overflows": [], "traced_points": []}
    kept = None
    t0 = time.perf_counter()
    while True:
        pts = sweep_points(config, traffic, seed, WINDOW, out["sweeps"])
        span = jax.profiler.TraceAnnotation(SWEEP) if trace \
            else nullcontext()
        with span:
            rows, flight = sweep(pts)
        out["compiles"] += flight["dispatch_stats"]["compiles"]
        out["launches"] += flight["dispatch_stats"]["dispatches"]
        out["overflows"] += flight["f32_overflows"]
        out["flow_slots"] += flow_slots(pts)
        out["points"] += len(pts)
        if trace:
            out["traced_points"] += pts
        if out["sweeps"] <= check_index:
            kept = (pts, [m.extra["raw"] for m in rows])
        out["sweeps"] += 1
        done = time.perf_counter() - t0 >= seconds
        if done and (not trace or out["sweeps"] >= MIN_TRACED_SWEEPS):
            break
    out["window_s"] = time.perf_counter() - t0
    return out, kept


def reference_rows(points):
    """The float64 reference of every point, on CPU worker processes
    that never touch JAX."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from reference import simulate

    n = max(1, min(len(points), (os.cpu_count() or 2) - 1, 16))
    if n == 1:
        return [simulate(p) for p in points]
    with ProcessPoolExecutor(
            max_workers=n,
            mp_context=multiprocessing.get_context("spawn")) as ex:
        return list(ex.map(simulate, points))


def load_reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics, ctx):
    """Each metric from its own reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peak_bytes(devices):
    """Peak device memory of the fullest chip: the allocator's peak of
    live buffers plus its peak of memory reserved for loaded programs'
    scratch, which `peak_bytes_in_use` leaves out."""
    def peak(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) + \
            int(st.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


def traced_window(cell, seed, seconds):
    """The window under the profiler, with host spans; returns the
    window's counts, the kept rows and the reduced trace."""
    import glob

    import jax

    import xplane
    from spans import host_spans

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # host spans come from TraceMe annotations; the Python function
    # tracer would slow every host layer it measures
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with host_spans():
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            win, kept = run_window(cell, seed, min(seconds, TRACE_SECONDS),
                                   trace=True)
        finally:
            jax.profiler.stop_trace()
    path = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)
    device_ops, spans = xplane.read_planes(path[0])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return win, kept, xplane.reduce(device_ops, spans)


def main(argv=None, *, require_tpu=True, cell=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = cell or resolve_cell(args.workload)

    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) != cell["chips"]):
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)
    from repro.experiments import enable_compile_cache

    enable_compile_cache(CACHE_DIR)

    warmups = set_up(cell, args.seed, args.seconds)
    setup_s = time.perf_counter() - T_START
    reduced = None
    if args.trace:
        win, kept, reduced = traced_window(cell, args.seed, args.seconds)
    else:
        win, kept = run_window(cell, args.seed, args.seconds, trace=False)
    memory_peak = peak_bytes(devices)
    gc.collect()

    from compare import branch, checks, summary

    t = time.perf_counter()
    refs = reference_rows(kept[0])
    div = summary(refs, kept[1], [branch(p) for p in kept[0]])
    chk = checks(div, cell["limits"])
    reference_s = time.perf_counter() - t
    correct = all(c["ok"] for c in chk.values()) and not win["overflows"]

    dev = devices[0]
    ctx = dict(win, setup_s=setup_s, warmup_sweeps=warmups,
               trace=reduced, device_kind=dev.device_kind,
               memory_peak_bytes=memory_peak, bench_dir=BENCH)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": win["points"], "failed": 0}
    if args.trace:
        result["metrics"] = read_metrics(cell["per_layer"], ctx)
        if reduced:
            device["busy_s"] = reduced["busy_ns"] / 1e9
            device["window_s"] = reduced["window_ns"] / 1e9
    else:
        result["metrics"] = read_metrics(cell["end_to_end"], ctx)
    result["device"] = device
    if reduced:
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in reduced["top_ops"]],
            "idle_gaps": [[n, ns / 1e9]
                          for n, ns in reduced["idle_by_span"]]}
    result["window"] = {"sweeps": win["sweeps"], "seconds": win["window_s"],
                        "compiles": win["compiles"],
                        "launches": win["launches"],
                        "warmup_sweeps": warmups,
                        "reference_s": reference_s}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in chk.items()}
    for k, c in chk.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
