#!/usr/bin/env python3
"""Prove that the simulator's main path runs on a TPU.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the lane mesh only

Every phase goes through `execute_points(backend="jax")` — the megabatch
dispatch that `run_experiment` uses — in float32, with the engine's
Pallas kernels compiled by Mosaic, and is checked against the NumPy
engine (the reference):

  (a) the acceptance grid on `flap_during_incast`: 3 routings x 2 NIC
      stacks x 3 fault fractions x 2 seeds = 36 points in one launch
      and one compile;
  (b) `giga_fabric_storage` at registry size: 4,096 hosts, 102,400
      flows, 60 slots, 8 link kills (sparse aggregation);
  (c) the `train_step_flap` schedule workload (phase-timeline operands).

`--chips 4` runs only grid (a), its lanes sharded over four chips, and
compares it point by point with the same grid on one chip (in this
process) and with the NumPy reference.

float32 trajectories fork from the float64 reference where a queue sits
on an ECN threshold, so each comparison bounds how far the fork spreads
(`BOUNDS`, derived from XLA-CPU float32 rehearsals of these phases)
instead of demanding equality.  A bound that fails, a device that is not
a TPU, or a megabatch program without Mosaic kernels exits non-zero.
Earlier lines report each phase as JSON; the last line is
`{"ok": true, "device": {...}}`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# a flow "forked" when its mean goodput moved more than this from the
# reference (line-rate units); a point "tracks" the reference when no
# flow moved more than TRACK_TOL, and only then are the last slot's link
# loads compared
FORK_TOL = 1e-3
TRACK_TOL = 1e-5

# Worst divergence from the NumPy reference each phase may show (see
# `worst`).  An XLA-CPU float32 rehearsal of each phase, with the kernels
# in interpret mode and off (the two agree to the bit), gave:
#   grid      forked 0.2, max 3.9e-3, mean 2.4e-4, completion 0,
#             total 6.3e-3, util 2.1e-7 on 24 tracking points;
#   giga      forked 1.25e-3, max 1.5e-2, mean 8.9e-8, completion 0,
#             total 2.7e-6, no tracking point;
#   schedule  every metric below 1e-7.
# The chip's float32 arithmetic forks other trajectories, so each bound
# leaves room for more forks while holding the averages close; widen
# none without saying why in CHANGES.md.
BOUNDS = {
    "grid": {"forked_frac": 0.5, "max_abs_goodput": 0.05,
             "mean_goodput_diff": 5e-3, "completion_diff_frac": 0.05,
             "total_goodput_rel": 0.05, "util_p99": 1e-3},
    "giga": {"forked_frac": 0.05, "max_abs_goodput": 0.05,
             "mean_goodput_diff": 1e-3, "completion_diff_frac": 0.05,
             "total_goodput_rel": 0.02, "util_p99": 1e-3},
    "schedule": {"forked_frac": 0.1, "max_abs_goodput": 0.05,
                 "mean_goodput_diff": 1e-3, "completion_diff_frac": 0.1,
                 "total_goodput_rel": 0.01, "util_p99": 1e-3},
}
# the sharded grid runs the one-chip program's per-lane computation, so
# it is held to the grid's bounds against the one-chip run too
BOUNDS["mesh_vs_one_chip"] = BOUNDS["grid"]

RAW_FIELDS = ("mean_goodput", "completion_slot", "total_goodput",
              "util_up_last")


def _raw(spec, compiled, result):
    """`derive` hook: keep the per-flow and per-link arrays of a point
    (either engine) for the comparison."""
    import numpy as np

    return {"raw": {f: np.array(getattr(result, f)) for f in RAW_FIELDS}}


def divergence(ref: dict, got: dict) -> dict:
    """How far one point's float32 run strays from its reference.  Per
    flow and in total only time averages are compared: a forked
    trajectory keeps its averages but shifts the phase of every
    sawtooth, so one slot's snapshot says little."""
    import numpy as np

    r, g = ref["mean_goodput"], got["mean_goodput"]
    d = np.abs(g - r)
    total_r = float(np.mean(ref["total_goodput"]))
    du = np.abs(got["util_up_last"] - ref["util_up_last"]).ravel()
    return {
        "forked_frac": float(np.mean(d > FORK_TOL)),
        "max_abs_goodput": float(d.max()),
        "mean_goodput_diff": float(abs(g.mean() - r.mean())),
        "completion_diff_frac": float(np.mean(
            got["completion_slot"] != ref["completion_slot"])),
        "total_goodput_rel": abs(float(np.mean(got["total_goodput"]))
                                 - total_r) / max(total_r, 1e-12),
        "util_p99": float(np.quantile(du, 0.99)),
    }


def worst(refs, gots) -> dict:
    """Worst divergence over points, metric by metric.  The last slot's
    link utilization is a snapshot of a sawtooth, so it is held only on
    points that track the reference (`tracking` counts them)."""
    rows = [divergence(r, g) for r, g in zip(refs, gots)]
    out = {k: max(row[k] for row in rows) for k in rows[0]}
    tracking = [row for row in rows if row["max_abs_goodput"] <= TRACK_TOL]
    out["util_p99"] = max([row["util_p99"] for row in tracking],
                          default=0.0)
    out["tracking"] = len(tracking)
    return out


def check(name: str, div: dict, bounds: dict) -> None:
    over = {k: (div[k], b) for k, b in bounds.items() if div[k] > b}
    if over:
        raise SystemExit(f"{name}: divergence over its bounds "
                         f"(value, bound): {over}")


def numpy_reference(points):
    from repro.experiments import execute_points

    t0 = time.perf_counter()
    ms = execute_points(points, backend="numpy", derive=_raw,
                        processes=min(len(points), os.cpu_count() or 1,
                                      8))
    return [m.extra["raw"] for m in ms], time.perf_counter() - t0


def program_facts(points, n_devices=None) -> dict:
    """Compile the megabatch program(s) these points launch and report
    what the compiler made of them: Mosaic kernels present
    (`tpu_custom_call`), compile seconds and the temporary memory."""
    from repro.netsim.jx.megabatch import megabatch_programs
    from repro.scenarios import compile_scenario

    import jax

    progs = megabatch_programs([compile_scenario(p) for p in points],
                               n_devices)
    t0 = time.perf_counter()
    kernels, temp, devices = 0, 0, 0
    for fn, args in progs:
        with warnings.catch_warnings():
            # the scan rewrites the donated carry; not every leaf aliases
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = fn.lower(*args).compile()
        kernels += compiled.as_text().count("tpu_custom_call")
        temp = max(temp, compiled.memory_analysis().temp_size_in_bytes)
        devices = max(devices, len(jax.tree.leaves(
            compiled.input_shardings)[0].device_set))
    facts = {"programs": len(progs),
             "compile_s": time.perf_counter() - t0,
             "tpu_custom_call": kernels, "temp_bytes": temp,
             "program_devices": devices}
    if kernels == 0:
        raise SystemExit("the megabatch program holds no Mosaic kernel "
                         "(tpu_custom_call): the Pallas kernels were "
                         "interpreted or replaced by their jnp oracles")
    return facts


def run_jax(points) -> tuple:
    """Cold then warm `execute_points`; returns the warm run's raw
    arrays and the flight-record facts of both."""
    from repro.experiments import execute_points

    out = {}
    for run in ("cold", "warm"):
        flight: dict = {}
        t0 = time.perf_counter()
        ms = execute_points(points, backend="jax", derive=_raw,
                            flight=flight)
        out[f"{run}_s"] = time.perf_counter() - t0
        out[f"{run}_launches"] = flight["pipeline"]["launches"]
        out[f"{run}_compiles"] = flight["dispatch_stats"]["compiles"]
        if flight["f32_overflows"]:
            raise SystemExit(f"float32 bytes_total overflow: "
                             f"{flight['f32_overflows']}")
    return [m.extra["raw"] for m in ms], out


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def report(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def grid_points():
    """The acceptance grid on `flap_during_incast`: routing × nic ×
    fault-frac (rescaling the scenario's first fault) × seed."""
    from repro.experiments import Axis, Experiment, product

    axes = product(Axis("sim.routing", ("ar", "war", "ecmp")),
                   Axis("sim.nic", ("spx", "dcqcn")),
                   Axis("faults[0].frac", (0.3, 0.5, 0.8)),
                   Axis("seed", (0, 1)))
    exp = Experiment(name="chip_smoke.grid", base="flap_during_incast",
                     axes=axes)
    points = [p.spec for p in exp.points()]
    if len(points) != 36:
        raise SystemExit(f"grid: expected 36 points, got {len(points)}")
    return points


def compare(phase: str, points, bounds: dict) -> tuple:
    """Run `points` through the NumPy reference and the JAX path, hold
    the JAX run to `bounds`, and return `(refs, gots, facts)`.  The
    first launch must be one launch and one compile per program."""
    refs, np_s = numpy_reference(points)
    facts = program_facts(points)
    gots, run = run_jax(points)
    if (run["cold_launches"], run["cold_compiles"]) != \
            (facts["programs"],) * 2:
        raise SystemExit(f"{phase}: expected {facts['programs']} "
                         f"launch(es) and compile(s), got {run}")
    div = worst(refs, gots)
    check(phase, div, bounds)
    return refs, gots, dict(points=len(points), numpy_s=np_s, **facts,
                            **run, divergence=div)


def phase_grid() -> dict:
    """(a): the 36-point acceptance grid — one launch, one compile."""
    _, _, facts = compare("grid", grid_points(), BOUNDS["grid"])
    if facts["programs"] != 1:
        raise SystemExit(f"grid: {facts['programs']} programs, not 1")
    return dict(**facts, peak_bytes=peak_bytes())


def phase_scenario(phase: str, name: str) -> dict:
    """(b), (c): one registry scenario at its registry size."""
    from repro.scenarios import get_scenario

    spec = get_scenario(name).with_sim(backend="jax")
    refs, _, facts = compare(phase, [spec], BOUNDS[phase])
    return dict(scenario=name, flows=int(refs[0]["mean_goodput"].size),
                hosts=spec.topo.n_hosts, slots=spec.sim.slots, **facts,
                peak_bytes=peak_bytes())


def phase_mesh(n_chips: int) -> dict:
    """Grid (a) with its lanes sharded over `n_chips` chips, against the
    same grid on one chip and against the NumPy reference."""
    import numpy as np

    from repro.netsim.jx.megabatch import run_megabatch
    from repro.scenarios import compile_scenario

    points = grid_points()
    _, gots, facts = compare("mesh vs numpy", points, BOUNDS["grid"])
    if facts["program_devices"] != n_chips:
        raise SystemExit(f"mesh: the program spans "
                         f"{facts['program_devices']} devices, not "
                         f"{n_chips}")
    t0 = time.perf_counter()
    ones = [{f: np.array(getattr(r, f)) for f in RAW_FIELDS}
            for r in run_megabatch([compile_scenario(p) for p in points],
                                   n_devices=1)]
    one_s = time.perf_counter() - t0
    div_one = worst(ones, gots)
    check("mesh vs one chip", div_one, BOUNDS["mesh_vs_one_chip"])
    identical = all(np.array_equal(a[f], b[f]) for a, b in zip(ones, gots)
                    for f in RAW_FIELDS)
    return dict(chips=n_chips, **facts, one_chip_s=one_s,
                divergence_vs_one_chip=div_one,
                bit_identical_to_one_chip=identical,
                peak_bytes=peak_bytes())


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the lane-mesh check on four chips")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)

    from repro.experiments import enable_compile_cache
    from repro.kernels.backend import pallas_enabled

    if not pallas_enabled():
        print("chip_smoke: REPRO_NETSIM_PALLAS turns the Pallas kernels "
              "off; the smoke test runs them", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    report("setup", device=device, jax=jax.__version__,
           compile_cache=enable_compile_cache())
    if args.chips == 1:
        report("grid", **phase_grid())
        report("giga", **phase_scenario("giga", "giga_fabric_storage"))
        report("schedule", **phase_scenario("schedule", "train_step_flap"))
    else:
        report("mesh", **phase_mesh(args.chips))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
