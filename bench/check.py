"""The comparison that decides ``correct``.

The numbers, each against its limit in ``bench/limits/<cell>.json``, which
names the ones a cell holds:

* ``loss_gap``    — the largest relative gap between the program's loss
  and the reference's over the first steps;
* ``grad_gap``    — the first gradient as the optimizer got it: over the
  leaves, the largest gap between the program's leaf norm and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf;
* ``update_gap``  — the same of the parameters' change over the first
  steps, leaving out the leaves whose reference gradient is under a
  thousandth of the median leaf's: Adam moves those by round-off alone
  (a key bias under softmax has no gradient at all);
* ``grad_diff``   — the first gradient again (the first moment after one
  step, in the moment type): the median over the leaves of the norm of
  the program's leaf less the reference's, over the same scale;
* ``update_diff`` — the same of the parameters' change, over the leaves
  ``update_gap`` keeps.

A gap of norms sees a step that is not taken, is taken twice or sees
other rows; rounding moves each element a little and the norms hardly at
all.  A norm of a difference sees rounding: a precision below the
configured one moves every element of every leaf, so the median leaf
moves with it, while one leaf the program sums coarsely does not.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, Tuple

NAMES = ("loss_gap", "grad_gap", "update_gap", "grad_diff", "update_diff")
QUIET = 1e-3


def _gap(a: float, b: float, scale: float) -> float:
    """|a - b| / scale; infinite where the program's number is not finite
    (a NaN would otherwise compare as no larger than any gap)."""
    g = abs(a - b) / scale
    return g if math.isfinite(g) else math.inf


def _scaled(num: Dict[str, float], ref: Dict[str, float], keys
            ) -> Dict[str, float]:
    """Per leaf of ``keys``: ``num`` over the larger of the reference's
    norm of that leaf and of the median leaf."""
    floor = statistics.median(ref[k] for k in keys)
    return {k: _gap(num[k], 0.0, max(ref[k], floor, 1e-30)) for k in keys}


def _keys(ref: Dict[str, Any]) -> Dict[str, list]:
    keys = sorted(ref["grad"])
    g_floor = statistics.median(ref["grad"].values())
    return {"grad": keys,
            "change": [k for k in keys if ref["grad"][k] >= QUIET * g_floor]}


def leaf_diffs(a: Dict[str, Any], b: Dict[str, Any]
               ) -> Dict[str, Tuple[float, float]]:
    """Per leaf of two ``{path: array}`` trees: (||a - b||, ||b||), worked
    out in float32 on JAX's default device."""
    import jax
    import jax.numpy as jnp

    if set(a) != set(b):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(a) ^ set(b))}")

    def norms(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((x - y) ** 2)), jnp.sqrt(jnp.sum(y * y))
    got = jax.jit(lambda A, B: {k: norms(A[k], B[k]) for k in A})(a, b)
    return {k: (float(d), float(n)) for k, (d, n) in got.items()}


def per_leaf(prog: Dict[str, Any], ref: Dict[str, Any]
             ) -> Dict[str, Dict[str, float]]:
    """Each per-leaf reading, scaled, by leaf: ``grad_gap``, ``update_gap``
    (gaps of norms), ``grad_diff``, ``update_diff`` (norms of differences).
    """
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    keys = _keys(ref)
    out = {}
    for name, what in (("grad", "grad"), ("update", "change")):
        ks = keys[what]
        out[f"{name}_gap"] = _scaled(
            {k: abs(prog[what][k] - ref[what][k]) for k in ks}, ref[what], ks)
        diffs = leaf_diffs(prog[f"{what}_arrays"], ref[f"{what}_arrays"])
        out[f"{name}_diff"] = _scaled(
            {k: diffs[k][0] for k in ks}, {k: diffs[k][1] for k in ks}, ks)
    return out


def gaps(prog: Dict[str, Any], ref: Dict[str, Any],
         leaves: Dict[str, Dict[str, float]] = None) -> Dict[str, float]:
    """The numbers from two sets of readings (``reference.readings`` and
    ``run.set_up`` give the same form)."""
    leaves = per_leaf(prog, ref) if leaves is None else leaves
    loss = max(_gap(p, r, abs(r)) for p, r in zip(prog["loss"], ref["loss"]))
    return {"loss_gap": loss,
            "grad_gap": max(leaves["grad_gap"].values()),
            "update_gap": max(leaves["update_gap"].values()),
            "grad_diff": statistics.median(leaves["grad_diff"].values()),
            "update_diff": statistics.median(leaves["update_diff"].values())}


def judge(numbers: Dict[str, float], limits: Dict[str, Any]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each number the limits name beside its limit.  A
    number that is not finite fails."""
    out, ok = {}, True
    for name in NAMES:
        if name not in limits:
            continue
        v, lim = float(numbers[name]), float(limits[name])
        out[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    if not out:
        raise ValueError(f"the limits name none of {NAMES}")
    return ok, out
