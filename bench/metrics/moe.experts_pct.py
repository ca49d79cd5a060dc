"""moe.experts_pct: the share of the traced window in which ops of the
``moe.experts`` scope run (the grouped expert FFN and the shared expert;
all phases), in %; the chip where it is largest.  Nothing for a dense
model.  Read from the step's scope map in ``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("moe.experts"))
