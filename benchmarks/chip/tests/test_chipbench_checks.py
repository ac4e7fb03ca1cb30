"""The training comparison by leaf: the compared step number is the
median moved leaf's gap, so one leaf whose AdamW step rounding turns
into size moves only the worst-leaf number, which is not compared; a
state left unchanged reads 1 either way; leaves with no gradient to
speak of are left out."""

import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import pytest  # noqa: E402

import checks  # noqa: E402

LEAVES = {"a": 1.0, "b": 2.0, "c": 0.5, "d": 4.0, "e": 1.5}


def _ref():
    return {"losses": [1.0, 0.9, 0.8], "grad": dict(LEAVES),
            "change": dict(LEAVES), "raw_grad": dict(LEAVES)}


def _prog(**change):
    return {"losses": [1.0, 0.9, 0.8], "grad": dict(LEAVES),
            "change": dict(LEAVES, **change)}


def test_one_noisy_leaf_moves_the_worst_leaf_not_the_median():
    got = checks.compare_train(_prog(c=0.5 + 1.5e-4), _ref())
    assert got["step_gap_max"] == pytest.approx(1e-4)
    assert got["step_gap_med"] == 0.0
    assert got["loss_rel"] == 0.0 and got["grad_gap"] == 0.0


def test_an_unchanged_state_reads_one():
    got = checks.compare_train(_prog(**{k: 0.0 for k in LEAVES}), _ref())
    assert got["step_gap_med"] == pytest.approx(1.0)
    assert got["step_gap_max"] == pytest.approx(1.0)


def test_leaves_with_no_gradient_are_not_compared():
    ref = _ref()
    ref["raw_grad"]["e"] = 1e-6          # under a thousandth of the median
    got = checks.compare_train(_prog(e=9.0), ref)
    assert checks.moved_leaves(ref["raw_grad"]) == ["a", "b", "c", "d"]
    assert got["step_gap_max"] == 0.0
