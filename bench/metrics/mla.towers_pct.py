"""mla.towers_pct: the share of the traced window in which ops of the
``mla.towers`` scope run (latent attention's query projection, the
key-value latent and its norm, the key and value up-projections and the
rope; all phases), in %; the chip where it is largest.  Nothing for a
model without latent attention.  Read from the step's scope map in
``ctx["scopes"]``."""

from bench.scopes import in_layers, share


def compute(trace, ctx):
    return share(trace, ctx, in_layers("mla.towers"))
