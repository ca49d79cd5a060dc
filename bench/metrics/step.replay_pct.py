"""step.replay_pct: the share of the traced window in which the model's
forward runs a second time, in %; the chip where it is largest.

The backward tick (``tick.B``) pulls its gradients through ``jax.vjp``,
which runs the chunk's forward again before the backward; those ops carry
``jvp(...)`` and no ``transpose(...)`` in their ``op_name`` path, under a
model layer scope (``repro.scopes``).  Read from the step's scope map in
``ctx["scopes"]``; nothing where it is absent."""

from bench.scopes import is_replay, share


def compute(trace, ctx):
    return share(trace, ctx, is_replay)
