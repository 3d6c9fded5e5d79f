"""The training comparison against numbers worked by hand."""

import math

import pytest

from bench import check
from bench.reference.train import Readings


def readings(loss, grad, change):
    return Readings(list(loss), dict(grad), dict(change))


def test_worst_leaf_gaps():
    ref = readings([10.0, 9.0], {"a": 1.0, "b": 2.0, "c": 4.0}, {"a": 1.0, "b": 2.0, "c": 4.0})
    cand = readings([10.5, 9.0], {"a": 1.1, "b": 2.0, "c": 3.0}, {"a": 1.0, "b": 2.2, "c": 4.0})
    got = check.compare(ref, cand)
    assert got["loss1_gap"] == pytest.approx(0.5) and got["loss2_gap"] == 0.0
    # the median leaf's norm (2) is the floor under a: |1.1 - 1| / 2, |0| / 2, |3 - 4| / 4
    assert got["grad_gap"] == pytest.approx(0.25) and got["grad_leaf"] == "c"
    assert got["change_gap"] == pytest.approx(0.1) and got["change_leaf"] == "b"


def test_quiet_leaves_leave_the_change_and_missing_leaves_fail():
    ref = readings([1.0], {"a": 1.0, "b": 1.0, "q": 1e-6}, {"a": 1.0, "b": 1.0, "q": 1.0})
    cand = readings([1.0], {"a": 1.0, "b": 1.0, "q": 1e-6}, {"a": 1.0, "b": 1.0, "q": 5.0})
    assert check.compare(ref, cand)["change_gap"] == 0.0
    del cand.grad["q"]
    got = check.compare(ref, cand)
    assert math.isinf(got["grad_gap"])
    ok, shown = check.judge(got, {"grad_gap": 0.1})
    assert not ok and shown["grad_gap"]["limit"] == 0.1
