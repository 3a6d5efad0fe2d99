"""The control comes out as not correct: the comparison catches answers
lost across partitions."""
import numpy as np
import pytest

import control
import run


def test_spanning_rows():
    assignment = np.array([0, 0, 1, 1])
    rows = np.array([[0, 1, -1], [0, 2, -1], [3, -1, -1]])
    assert control.spanning(rows, assignment).tolist() == [False, True, False]


@pytest.mark.parametrize("seed", [3, 101, 2**31 + 7])
def test_control_is_not_correct(seed):
    cell = run.load_cell("subgen-400k-k4-resident.paper-closed", rehearse=True)
    r = control.readings(cell, seed, 30)
    assert r["correct"] is False
    assert r["checks"]["mismatched_rows"]["value"] > 0
    assert r["spanning_answers"]["Q4"] > 0
