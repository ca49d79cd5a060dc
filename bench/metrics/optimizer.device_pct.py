"""optimizer.device_pct: the share of the traced window in which ops of
the ``optimizer`` scope (mean over microbatches, AdamW, the ZeRO
constraints) or the ``grad_accum`` scope (each microbatch's fp32 gradient
adds) run, in %; the chip where it is largest.  Read from the step's
scope map in ``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("optimizer", "grad_accum"))
