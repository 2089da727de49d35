#!/usr/bin/env python3
"""The program's own measurement, read from a profiler trace of a cell.

    python3 bench/program_trace.py --workload <cell> --seed <n> [--seconds 8]

Runs the cell's set-up (`run.set_up`) and one window of whole sweeps
(`run.run_window`) under the profiler with the Python tracer off, keeps
the trace under `.bench_trace/program/` for reading by hand, and prints
one JSON line:

  spans_s_per_sweep   host seconds per traced sweep of every `repro.*`
                      span of the flight recorder (`repro.netsim.flight`)
  counters            the window's flight-recorder counters
  setup_counters      the same over set-up
  metrics             the per-layer numbers these give: flow prep, ECMP
                      replay, launch and device-wait seconds per sweep,
                      the pad-flow share, the route stage's share of
                      device busy time, set-up compile seconds
  stages_s            device time of the ops under each `slot/<stage>`
                      scope of the slot step, and the share of op time
                      and of busy time the scopes cover
  unscoped_top_s      the ops no stage scope covers, by device time
  idle_s              device 0's idle time in the window, each idle
                      stretch split over its whole length by the
                      innermost `repro.*` span covering each part
  kernels             (xplane.kernel_of's guess, the kernel's `name`)
                      pairs, with device time

A device op's event in the trace carries its HLO instruction's text but
not the instruction's `metadata={op_name=...}`, where the scopes are.
So the stage of an op is looked up by instruction name in the compiled
text of the program the sweeps ran (`op_names`); a kernel's instruction
is named after the kernel's `name=`.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import xplane  # noqa: E402

SPAN_PREFIX = "repro."
OUTSIDE = "outside any span"
TRACE_DIR = os.path.join(ROOT, ".bench_trace", "program")
_STAGE = re.compile(r"(?:^|/)slot/(\w+)")
_META = re.compile(r"#.*#$")
_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_CALLEE = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
# xplane.kernel_of's guess -> the kernel's `name=` in the program
KERNEL_NAMES = {
    "_plane_split_kernel": "plane_split",
    "_pair_score_kernel": "pair_fractions",
    "_bottleneck_kernel": "bottleneck",
    "_load_bottleneck_kernel": "bucket_load_bottleneck",
    "_queue_update_kernel": "queue_update",
    "_nic_update_kernel": "nic_update",
}


def span_name(name: str) -> str:
    """A host event's name without TraceMe `#k=v#` metadata."""
    return _META.sub("", name)


def program_spans(path: str) -> List[Tuple[int, int, str, Optional[int]]]:
    """`[(start, end, name, sweep)]` of every `repro.*` host annotation
    of an `.xplane.pb` (`xplane.read_planes` keeps `bench.*` only)."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = span_name(ev.name)
                if name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns), name,
                                  dict(ev.stats).get("sweep")))
    return spans


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> `op_name` path of a compiled program's text.
    XLA's TPU compiler leaves a scatter-add, and the fusions around it,
    without an op name; the scatter's reduction computation keeps one
    (`slot/route/add`).  So an instruction without an op name takes,
    through the computation it calls (`calls=`, `to_apply=`), the name
    of that computation's scatter, else of its root."""
    paths: Dict[str, Optional[str]] = {}
    callee: Dict[str, str] = {}
    body: Dict[str, List[Tuple[str, str, bool]]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        text = line.strip()
        if " = " not in text:
            if text.endswith("{"):
                words = text.split()
                comp = words[words[0] == "ENTRY"].lstrip("%")
            continue
        root = text.startswith("ROOT ")
        name, opcode, _, _ = xplane.parse_op(text[5:] if root else text)
        m = _OP_NAME.search(text)
        paths[name] = m.group(1) if m else None
        body[comp].append((name, opcode, root))
        c = _CALLEE.search(text) if opcode in ("fusion", "scatter") \
            else None
        if c:
            callee[name] = c.group(1)

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        if paths.get(name) or depth > 8 or name not in callee:
            return paths.get(name)
        inner = body.get(callee[name], ())
        for pick in ([n for n, op, _ in inner if op == "scatter"]
                     + [n for n, _, root in inner if root]):
            found = resolve(pick, depth + 1)
            if found:
                return found
        return None

    return {n: p for n, p in ((n, resolve(n)) for n in paths) if p}


def stage_of(path: str) -> Optional[str]:
    m = _STAGE.search(path)
    return m.group(1) if m else None


def split_idle(ops, spans, window: Tuple[int, int]) -> Dict[str, int]:
    """Idle nanoseconds of one device inside `window`, each idle stretch
    split over its whole length by the innermost (shortest) span that
    covers each part of it; parts no span covers go to `OUTSIDE`.
    `ops` are `(start, end, ...)`, `spans` `(start, end, name, ...)`."""
    w0, w1 = window
    busy = xplane._merged([(max(s, w0), min(e, w1)) for s, e, *_ in ops
                           if e > w0 and s < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    out: Dict[str, int] = defaultdict(int)
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        inside = [sp for sp in spans if sp[1] > a and sp[0] < b]
        cuts = sorted({a, b} | {t for sp in inside for t in sp[:2]
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [sp for sp in inside if sp[0] <= x and y <= sp[1]]
            inner = min(cover, key=lambda sp: sp[1] - sp[0],
                        default=None)
            out[inner[2] if inner else OUTSIDE] += y - x
    return dict(out)


def stage_ns(ops, window: Tuple[int, int], names: Dict[str, str]):
    """Device time per slot-step stage scope inside `window`, loop and
    call containers left out; with the op time in all, the kernels'
    (guess, name) pairs with their time, and the time of each op no
    stage scope covers (by `xplane.label`, with its op-name path).
    `names` maps instruction names to op-name paths (`op_names`)."""
    w0, w1 = window
    stages: Dict[str, int] = defaultdict(int)
    kernels: Dict[Tuple, int] = defaultdict(int)
    unscoped: Dict[Tuple, int] = defaultdict(int)
    total = 0
    for s, e, text in ops:
        if e <= w0 or s >= w1:
            continue
        d = min(e, w1) - max(s, w0)
        name, opcode, _, _ = xplane.parse_op(text)
        if opcode in xplane.CONTAINERS:
            continue
        total += d
        st = stage_of(names.get(name, ""))
        if st:
            stages[st] += d
        else:
            unscoped[(xplane.label(text), names.get(name))] += d
        guess = xplane.kernel_of(text)
        if guess:
            named = re.sub(r"\.\d+$", "", name)
            kernels[(guess, named if named in KERNEL_NAMES.values()
                     else None)] += d
    return dict(stages), total, dict(kernels), dict(unscoped)


def per_sweep(spans, window: Tuple[int, int]) -> Tuple[Dict, int]:
    """Seconds per sweep of each span name over the sweeps whose spans
    all lie inside `window`."""
    w0, w1 = window
    by_sweep = defaultdict(list)
    for sp in spans:
        by_sweep[sp[3]].append(sp)
    whole = [k for k, v in by_sweep.items() if k is not None
             and all(w0 <= s and e <= w1 for s, e, *_ in v)]
    tot: Dict[str, float] = defaultdict(float)
    for k in whole:
        for s, e, name, _ in by_sweep[k]:
            tot[name] += (e - s) / 1e9
    n = max(len(whole), 1)
    return {k: v / n for k, v in sorted(tot.items())}, len(whole)


def metrics_of(spans_s: Dict, counters: Dict, setup: Dict,
               stages: Dict, busy_ns: float) -> Dict:
    """The per-layer numbers the flight recorder gives."""
    def get(*names):
        return sum(spans_s.get(n, 0.0) for n in names)
    out = {
        "flow_prep_s_per_sweep": get("repro.prep.flows",
                                     "repro.prep.flow_arrays"),
        "ecmp_replay_s_per_sweep": get("repro.prep.ecmp_replay"),
        "launch_s_per_sweep": get("repro.launch"),
        "device_wait_s_per_sweep": get("repro.finalize.wait"),
        "setup_compile_s": setup.get("xla_compile_s", 0.0)
        + setup.get("cache_load_s", 0.0),
    }
    if counters.get("flow_slots_launched"):
        out["pad_flow_share"] = 100.0 * (
            1.0 - counters["flow_slots_real"]
            / counters["flow_slots_launched"])
    if busy_ns:
        out["route_stage_share"] = 100.0 * stages.get("route", 0) / busy_ns
    return out


def program_op_names(pts) -> Dict[str, str]:
    """Instruction name -> op-name path of the programs a sweep over
    `pts` launches, compiled as the sweep compiled them (the persistent
    cache hands back the same executables)."""
    import warnings

    from repro.netsim.jx.megabatch import megabatch_programs
    from repro.scenarios import compile_scenario
    from specs import scenario_spec

    names: Dict[str, str] = {}
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        for fn, a in megabatch_programs(
                [compile_scenario(scenario_spec(p)) for p in pts]):
            names.update(op_names(fn.lower(*a).compile().as_text()))
    return names


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    args = p.parse_args(argv)

    import run
    from specs import resolve_cell
    from spans import SWEEP, host_spans

    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    from repro.experiments import enable_compile_cache
    from repro.netsim.flight import dispatch_counts

    jax.config.update("jax_enable_x64", False)
    enable_compile_cache(run.CACHE_DIR)
    cell = resolve_cell(args.workload)
    dev = jax.devices()[0]

    run.set_up(cell, args.seed, args.seconds)
    setup = dispatch_counts()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with host_spans():
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            win, _ = run.run_window(cell, args.seed, args.seconds,
                                    trace=True)
        finally:
            jax.profiler.stop_trace()
    counters = {k: v - setup.get(k, 0) for k, v in dispatch_counts().items()}
    path = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)[0]
    device_ops, bench_spans = xplane.read_planes(path)
    reduced = xplane.reduce(device_ops, bench_spans)
    spans = program_spans(path)
    sw = [(s, e) for s, e, n in bench_spans if n == SWEEP]
    window = (min(s for s, _ in sw), max(e for _, e in sw))
    ops0 = device_ops.get(min(device_ops), []) if device_ops else []
    per = win["points"] // win["sweeps"]
    names = program_op_names(win["traced_points"][-per:])
    spans_s, n_sweeps = per_sweep(spans, window)
    stages, op_ns, kernels, unscoped = stage_ns(ops0, window, names)
    idle = split_idle(ops0, spans, window)
    busy = reduced.get("busy_ns", 0.0)
    idle_total = sum(idle.values())
    report = {
        "workload": cell["name"], "seed": args.seed, "trace": path,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "traced_sweeps": win["sweeps"], "whole_sweeps": n_sweeps,
        "spans_s_per_sweep": spans_s, "counters": counters,
        "setup_counters": setup,
        "metrics": metrics_of(spans_s, counters, setup, stages, busy),
        "stages_s": {k: v / 1e9 for k, v in sorted(stages.items())},
        "scoped_share_of_op_time": sum(stages.values()) / op_ns
        if op_ns else None,
        "scoped_share_of_busy": sum(stages.values()) / busy
        if busy else None,
        "unscoped_top_s": [[lab, p, ns / 1e9] for (lab, p), ns in
                           sorted(unscoped.items(),
                                  key=lambda kv: -kv[1])[:15]],
        "idle_s": {k: v / 1e9 for k, v in
                   sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_share_under_spans": 1 - idle.get(OUTSIDE, 0) / idle_total
        if idle_total else None,
        "midpoint_idle_s": [[n, v / 1e9] for n, v in
                            reduced.get("idle_by_span", [])],
        "kernels": [[g, n, ns / 1e9] for (g, n), ns in kernels.items()],
        "kernels_agree": all(KERNEL_NAMES.get(g) == n
                             for g, n in kernels),
        "busy_s": busy / 1e9, "window_s": reduced.get("window_ns", 0) / 1e9,
    }
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
