"""A run with the timed path broken underneath comes out not correct,
once for each fault a cell can have; the sound run comes out correct.

Most faults are planted where the program hands back each launch's
rows (`megabatch.finalize_group`):

  state_unchanged  the slot step returns its state unchanged: nothing
                   is delivered, no link is loaded;
  half_batch       half of the batch is left out: the second half of a
                   launch's points is filled with the mean over the
                   rest; a lone point's second half of flows delivers
                   nothing;
  answer_altered   the goodput answer is off by 1e-3 of line rate where
                   it is produced, for every flow of every point (and
                   the per-slot totals with it).  An
                   answer off by that much for one flow alone cannot be
                   told from a float32 fork (forked flows move by up to
                   a few 1e-3), so the check resolves faults of the
                   stage that produces the answers, not of one answer.

Two more break one part of the grid or of the routing:

  one_branch       the same altered answer, only on the points of the
                   cell's last NIC stack (planted where each point's
                   rows are distilled, `execute.distill_metrics`);
  ecmp_shifted     every ECMP flow hashed onto the next spine
                   (`engine._assign_for`, the host's path assignment
                   and its replay of the re-hash after faults).

The exchange between chips is not a fault these cells can have: the
lane mesh shards independent points and exchanges nothing.
"""
import numpy as np
import pytest

import run


def _state_unchanged(rows):
    for r in rows:
        r.mean_goodput = np.zeros_like(r.mean_goodput)
        r.total_goodput = np.zeros_like(r.total_goodput)
        r.util_up_last = np.zeros_like(r.util_up_last)
    return rows


def _half_batch(rows):
    n = len(rows)
    if n > 1:
        keep = rows[:n // 2]
        for r in rows[n // 2:]:
            for f in ("mean_goodput", "total_goodput", "util_up_last"):
                setattr(r, f, np.mean([getattr(k, f) for k in keep], 0))
        return rows
    # a lone point: the second half of its flows is left out, delivers
    # nothing, and the totals sum the rest
    r = rows[0]
    g = r.mean_goodput.copy()
    g[len(g) // 2:] = 0.0
    r.total_goodput = r.total_goodput * (g.sum() / r.mean_goodput.sum())
    r.mean_goodput = g
    return rows


def _alter(r):
    r.mean_goodput = r.mean_goodput + 1e-3
    r.total_goodput = r.total_goodput + 1e-3 * len(r.mean_goodput)


def _answer_altered(rows):
    for r in rows:
        _alter(r)
    return rows


ROW_FAULTS = {"state_unchanged": _state_unchanged,
              "half_batch": _half_batch, "answer_altered": _answer_altered}
FAULTS = ["sound", *ROW_FAULTS, "one_branch", "ecmp_shifted"]


def _plant(fault, cell, monkeypatch):
    import repro.experiments.execute as ex
    import repro.netsim.jx.engine as engine
    import repro.netsim.jx.megabatch as mb

    if fault in ROW_FAULTS:
        orig = mb.finalize_group
        monkeypatch.setattr(mb, "finalize_group",
                            lambda handle: ROW_FAULTS[fault](orig(handle)))
    elif fault == "one_branch":
        nic = cell["traffic"]["nic"][-1]
        distill = ex.distill_metrics

        def broken(spec, compiled, result):
            if spec.sim.nic == nic:
                _alter(result)
            return distill(spec, compiled, result)
        monkeypatch.setattr(ex, "distill_metrics", broken)
    elif fault == "ecmp_shifted":
        assign_for = engine._assign_for

        def shifted(cfg, *a, **kw):
            return (assign_for(cfg, *a, **kw) + 1) % cfg.n_paths
        monkeypatch.setattr(engine, "_assign_for", shifted)


def _run(cell, fault, monkeypatch, capsys):
    import json

    _plant(fault, cell, monkeypatch)
    assert run.main(["--workload", cell["name"], "--seed",
                     str(2 ** 33 + 17), "--seconds", "0.3", "--trace",
                     "0"], require_tpu=False, cell=cell) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", FAULTS)
def test_giga_faults(tiny_cell, bench_env, monkeypatch, capsys, fault):
    # the sparse aggregation path of the full-size fabric
    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    res = _run(tiny_cell("giga4096.storage_kills_ecmp"), fault,
               monkeypatch, capsys)
    assert res["correct"] is (fault == "sound"), res["checks"]
