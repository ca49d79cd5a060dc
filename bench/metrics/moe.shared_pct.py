"""moe.shared_pct: the share of the traced window in which ops of the
``moe.shared`` scope run (the shared experts, on every token; all
phases), in %; the chip where it is largest.  Nothing for a model without
shared experts.  Read from the step's scope map in ``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("moe.shared"))
