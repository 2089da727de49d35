"""The faults of `test_bench_faults.py` planted under the timed path of
the testbed grid, `testbed64.incast_flap_grid`, at test size; the sound
run comes out correct, every broken one not.

The grid is one launch of many small lanes over every routing and NIC
stack, so each fault of that file applies: the row faults to the whole
launch, `one_branch` to the last NIC stack's points, `ecmp_shifted` to
the ECMP rows.
"""
import pytest

from test_bench_faults import FAULTS, _run

CELL = "testbed64.incast_flap_grid"


@pytest.mark.parametrize("fault", FAULTS)
def test_testbed_faults(tiny_cell, bench_env, monkeypatch, capsys, fault):
    res = _run(tiny_cell(CELL), fault, monkeypatch, capsys)
    assert res["correct"] is (fault == "sound"), res["checks"]
