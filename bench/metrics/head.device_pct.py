"""head.device_pct: the share of the traced window in which ops of the
``head`` scope run (final norm, logits, cross-entropy; all phases), in %;
the chip where it is largest.  Read from the step's scope map in
``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("head"))
