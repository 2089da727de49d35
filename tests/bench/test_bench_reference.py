"""The plain reference: bit for bit the program's float64 NumPy engine
at small sizes, on every traffic mix it can run, and its bfloat16
control fails the comparison of every cell."""
import numpy as np
import pytest

from compare import branch, checks, summary
from reference import bf16, simulate
from specs import load_benchmark, scenario_spec, sweep_points

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
# the cell's mix and the mixes of the cells that wait on a program
# repair (PERF.md, Open questions): the testbed grid, ar / war routing
MIXES = [("testbed64", "incast_flap_grid"),
         ("giga4096", "storage_kills_ecmp"), ("giga4096", "kill_sweep_war")]
FIELDS = ("mean_goodput", "total_goodput", "util_up_last")


def _raw(spec, compiled, result):
    return {"raw": {f: np.array(getattr(result, f)) for f in FIELDS}}


def test_bf16_rounds_to_nearest_even():
    assert bf16(1.0) == 1.0
    assert bf16(1.0 + 2 ** -9) == 1.0                 # tie -> even
    assert bf16(1.0 + 3 * 2 ** -9) == 1.0 + 2 ** -7   # nearest
    assert bf16(1.0 + 2 ** -8 + 2 ** -12) == 1.0 + 2 ** -7
    x = np.array([0.1, -3.3, np.inf])
    assert np.all(np.isfinite(bf16(x)[:2]))
    assert bf16(x)[2] == np.inf


@pytest.mark.parametrize("config,traffic", MIXES)
def test_reference_matches_the_programs_numpy_engine(tiny_mix, config,
                                                     traffic):
    from repro.experiments import execute_points

    cell = tiny_mix(config, traffic)
    pts = sweep_points(cell["config"], cell["traffic"], 2 ** 33 + 11, 0, 0)
    pts = pts[::3] if len(pts) > 4 else pts
    rows = execute_points(
        [scenario_spec(p).with_sim(backend="numpy") for p in pts],
        backend="numpy", derive=_raw, processes=1)
    for p, m in zip(pts, rows):
        ref = simulate(p)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], m.extra["raw"][f])


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_fails_the_comparison(tiny_cell, name):
    """The control (the reference in bfloat16, put in the program's
    place) must come out not correct under the committed limits."""
    cell = tiny_cell(name)
    for seed in (3, 4, 5):
        pts = sweep_points(cell["config"], cell["traffic"], seed, 0, 0)
        numbers = summary([simulate(p) for p in pts],
                          [simulate(p, "bf16") for p in pts],
                          [branch(p) for p in pts])
        chk = checks(numbers, cell["limits"])
        assert not all(c["ok"] for c in chk.values()), numbers


def test_each_branch_is_held_apart():
    """A fault on one branch of six moves a median pooled over the grid
    not at all; the branch's own median shows it."""
    rng = np.random.default_rng(7)

    def row(off=0.0):
        g = rng.uniform(0.1, 0.5, 64)
        return {"mean_goodput": g, "total_goodput": np.full(10, g.sum()),
                "util_up_last": rng.uniform(0.1, 0.9, (1, 4, 4))}
    refs = [row() for _ in range(36)]
    gots = [{k: v.copy() for k, v in r.items()} for r in refs]
    branches = [(r, n) for r in ("ar", "war", "ecmp")
                for n in ("spx", "dcqcn") for _ in range(6)]
    for g, b in zip(gots, branches):
        if b == ("ecmp", "dcqcn"):
            g["mean_goodput"] = g["mean_goodput"] + 1e-3
    assert summary(refs, gots, branches)["p50_flow_abs"] > 9e-4
    assert summary(refs, gots, [0] * 36)["p50_flow_abs"] == 0.0
