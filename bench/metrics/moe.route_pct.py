"""moe.route_pct: the share of the traced window in which ops of the
``moe.route`` scope run (router, aux statistics, capacity positions,
dispatch scatter, the EP send bucket and both all-to-alls, combine; all
phases), in %; the chip where it is largest.  Nothing for a dense model.
Read from the step's scope map in ``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("moe.route"))
