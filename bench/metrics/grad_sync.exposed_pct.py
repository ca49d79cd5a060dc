"""grad_sync.exposed_pct: the share of the traced window in which a
collective of the ``grad_sync`` scope (the gradient sums after the tick
loop, ZeRO's reduce-scatter) runs on a chip and no other op does, in %;
the worst chip's.  Collectives are found by their op names as
``collectives.exposed_pct`` finds them; nothing where no collective
carries the scope (one chip).  Read from the step's scope map in
``ctx["scopes"]``."""

from bench.cells import load_metric
from bench.scopes import S

_is_collective = load_metric("collectives.exposed_pct").__globals__[
    "_is_collective"]


def compute(trace, ctx):
    smap = ctx.get("scopes")
    if not smap:
        return None

    def mine(name):
        return _is_collective(name) and \
            S.layer_of(smap.get(name, "")) == S.GRAD_SYNC

    if not any(mine(n) for ev in trace.devices.values() for n, _, _ in ev):
        return None
    win = trace.window_s() * 1e9
    return 100.0 * max(trace.alone_ns(d, mine) / win for d in trace.devices)
