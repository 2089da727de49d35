"""The row faults of `test_bench_faults.py` planted under the timed path
of the adaptive-routing cell, `giga4096.kill_sweep_war`, at test size;
the sound run comes out correct, every broken one not.

The cell's sweep is two launches: the pristine point alone, and the
faulted points in one batch.  So `half_batch` leaves out half of the
pristine point's flows (an eighth of the sweep's) and fills the faulted
batch's later rows with its first, which differs from them only after
the kills.  At the cell's size those rows part after the kills, and
`forked_frac` reads well over its limit on every seed; on the 128-host
test fabric they part only with more kills than the test size's halved
ones, so the test kills 0, 4, 8 and 16 links.

`one_branch` is `answer_altered` here (the cell has one branch) and
`ecmp_shifted` moves no flow of it (no flow routes by ECMP).
"""
import pytest

from test_bench_faults import ROW_FAULTS, _plant, _run

CELL = "giga4096.kill_sweep_war"


@pytest.mark.parametrize("fault", ["sound", *ROW_FAULTS])
def test_war_faults(tiny_cell, bench_env, monkeypatch, capsys, fault):
    # the sparse aggregation path of the full-size fabric
    monkeypatch.setenv("REPRO_JX_AGG", "sparse")
    _plant(fault, None, monkeypatch)
    cell = tiny_cell(CELL)
    cell["traffic"]["kills"] = [0, 4, 8, 16]
    res = _run(cell, fault, monkeypatch, capsys)
    assert res["correct"] is (fault == "sound"), res["checks"]
