"""attention.device_pct: the share of the traced window in which ops of
the ``attention`` scope run (ln1, the projections, flash or MLA, the TP
f/g; forward, replay and backward), in %; the chip where it is largest.
Read from the step's scope map in ``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("attention"))
