"""Faults planted under the timed path, to show that the check fails them.

Each wraps the program's step function (``run.set_up(step_wrapper=...)``):

* ``unchanged``  — the step returns the state it was given;
* ``half_batch`` — the loss leaves out the second half of every row's
  targets, the mean taken over the rest (the program's own loss mask);
* ``no_exchange`` — the all-to-alls between the expert shards are left
  out: each chip runs its own send buffer through its own experts.  Only
  where the configuration shards the experts (``applies``).

The controls (``reference.readings(precision="int8")`` and ``"fp8"`` in
the program's place) are the other things the check must fail;
``readings.py`` runs all of them on the chip, the tests at a small size
on the CPU.
"""

from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np


def unchanged(step_fn, prog):
    def step(state, batch):
        _, metrics = step_fn(state, batch)
        return state, metrics
    return step


def half_batch(step_fn, prog):
    b, s = int(prog.traffic["global_batch"]), int(prog.traffic["seq_len"])
    mask = np.zeros((b, s), np.float32)
    mask[:, : s // 2] = 1.0

    def step(state, batch):
        return step_fn(state, dict(batch, mask=jnp.asarray(mask)))
    return step


def no_exchange(step_fn, prog):
    def step(state, batch):
        # in force while the step is traced, so the compiled step has no
        # all-to-all
        with mock.patch.object(jax.lax, "all_to_all",
                               lambda x, *a, **k: x):
            return step_fn(state, batch)
    return step


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


def applies(name: str, config) -> bool:
    """Whether the fault ``name`` can occur in a cell of ``config``."""
    return name != "no_exchange" or int(config["parallel"]["ep"]) > 1
