"""The comparison that decides ``correct`` (``bench/check.py``)."""

import math

import numpy as np

from bench import check

LEAVES = ("a", "b", "c", "quiet")
REF = {"loss": [10.0, 9.0, 8.0],
       "grad": {"a": 1.0, "b": 2.0, "c": 4.0, "quiet": 1e-6},
       "change": {"a": 3.0, "b": 3.0, "c": 3.0, "quiet": 3.0},
       "grad_arrays": {k: np.full((4,), v / 2, np.float32) for k, v in
                       {"a": 1.0, "b": 2.0, "c": 4.0, "quiet": 1e-6}.items()},
       "change_arrays": {k: np.full((4,), 1.5, np.float32) for k in LEAVES}}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "update_gap": 0.1,
          "grad_diff": 0.1, "update_diff": 0.1}


def _prog(**kw):
    p = {k: (dict(v) if isinstance(v, dict) else list(v))
         for k, v in REF.items()}
    for k, v in kw.items():
        p[k].update(v) if isinstance(v, dict) else p.__setitem__(k, v)
    return p


def test_same_readings_pass():
    ok, numbers = check.judge(check.gaps(_prog(), REF), LIMITS)
    assert ok and all(n["value"] == 0 for n in numbers.values())
    assert list(numbers) == list(check.NAMES)


def test_gap_of_norms_against_leaf_or_median():
    # leaf a (1.0) is held against the median leaf's norm (1.5)
    prog = _prog(grad={"a": 1.3})
    g = check.gaps(prog, REF)
    assert math.isclose(g["grad_gap"], 0.3 / 1.5)
    by_leaf = check.per_leaf(prog, REF)["grad_gap"]
    assert max(by_leaf, key=by_leaf.get) == "a"


def test_quiet_leaves_leave_the_change_out():
    # under a thousandth of the median gradient: Adam moves it by round-off
    g = check.gaps(_prog(change={"quiet": 0.0}), REF)
    assert g["update_gap"] == 0.0
    g = check.gaps(_prog(change={"a": 0.0}), REF)
    assert g["update_gap"] == 1.0


def test_norm_of_difference_sees_what_norms_do_not():
    # a sign flip leaves every norm as it was; the difference is twice
    # the leaf; the median over leaves a, b, c, quiet
    flipped = {k: -v for k, v in REF["grad_arrays"].items()}
    g = check.gaps(_prog(grad_arrays=flipped), REF)
    assert g["grad_gap"] == 0.0
    # leaves' norms 1, 2, 4, 2e-6; median 1.5; scaled: 4/3, 2, 2, ~0
    assert math.isclose(g["grad_diff"], (4 / 3 + 2) / 2, rel_tol=1e-6)
    # one leaf off: the median leaf holds
    one = dict(REF["change_arrays"], c=np.zeros(4, np.float32))
    assert check.gaps(_prog(change_arrays=one), REF)["update_diff"] == 0.0


def test_loss_gap_is_the_worst_step():
    g = check.gaps(_prog(loss=[10.0, 9.009, 8.0]), REF)
    assert math.isclose(g["loss_gap"], 0.009 / 9.0)


def test_not_finite_fails():
    ok, numbers = check.judge(
        check.gaps(_prog(loss=[10.0, float("nan"), 8.0]), REF), LIMITS)
    assert not ok and numbers["loss_gap"]["value"] == math.inf
    ok, _ = check.judge(check.gaps(_prog(grad={"b": float("nan")}), REF),
                        LIMITS)
    assert not ok


def test_limits_name_the_numbers_held():
    ok, numbers = check.judge(check.gaps(_prog(), REF), {"loss_gap": 1e-3})
    assert ok and list(numbers) == ["loss_gap"]
