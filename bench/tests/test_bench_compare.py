"""The training comparison's arithmetic: per-leaf gaps of norms, their worst
and median, and which readings are compared against limits."""

import math

import numpy as np
import pytest

from bench import compare

REF = {"a": 1.0, "b": 2.0, "c": 4.0, "d": 1e-9}


def test_leaf_gaps_are_relative_to_the_leaf_or_the_median_leaf():
    prog = {"a": 1.1, "b": 2.0, "c": 4.0, "d": 0.2}
    gaps = compare.leaf_gaps(prog, REF)
    median = 1.5  # of 1e-9, 1, 2, 4
    assert gaps["a"] == pytest.approx(0.1 / median)
    assert gaps["b"] == 0.0 and gaps["c"] == 0.0
    # a leaf that hardly moves is measured against the median leaf
    assert gaps["d"] == pytest.approx((0.2 - 1e-9) / median)
    assert compare.worst_leaf_gap(prog, REF) == (gaps["d"], "d")
    assert compare.median_leaf_gap(prog, REF) == pytest.approx(
        np.median(list(gaps.values())))
    # a skipped leaf takes no part, in the median leaf either
    assert compare.worst_leaf_gap(prog, REF, skip={"d"})[1] == "a"


@pytest.mark.parametrize("reduce", [compare.worst_leaf_gap,
                                    compare.median_leaf_gap])
def test_a_nan_leaf_fails(reduce):
    prog = {**REF, "b": float("nan")}
    value = reduce(prog, REF)
    value = value[0] if isinstance(value, tuple) else value
    assert math.isnan(value)
    assert not compare.Check("x", value, 1.0).ok


def _run(losses, change, grads=1.0):
    return {"losses": losses, "grads": {"w": np.full(3, grads), "v": np.ones(2)},
            "params0": {"w": np.zeros(3), "v": np.zeros(2)},
            "params": {"w": np.full(3, change), "v": np.ones(2)}}


def test_training_checks_compare_the_first_step_and_the_median_leaf():
    ref = _run([[2.0, -1.0], [3.0, -2.0], [4.0, -3.0]], 1.0)
    # later steps and one leaf's change drift: reported, not compared
    prog = _run([[2.0, -1.0], [3.3, -2.0], [4.0, -3.0]], 1.5)
    limits = {"loss1": 1e-6, "grad1": 1e-6, "dparam3_median": 0.5}
    checks, uncompared = compare.training_checks(prog, ref, limits)
    by = {c.name: c for c in checks}
    assert list(by) == ["loss1", "grad1", "dparam3_median"]
    assert all(c.ok for c in checks), checks
    assert uncompared["loss_all_steps"] == pytest.approx(0.3 / 4.0)
    assert uncompared["dparam3_worst_leaf"] == "['w']"
    # a first step's loss off, or a gradient leaf off, fails
    prog["losses"][0][1] = -1.1
    prog["grads"]["v"] = np.full(2, 1.01)
    checks, _ = compare.training_checks(prog, ref, limits)
    assert [c.ok for c in checks] == [False, False, True]
