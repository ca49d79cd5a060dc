"""gmm_fwd_roofline: the grouped expert matmul kernel's share of its
roofline, in %.

Each of the layer's three calls (gate, up, down) multiplies the routed
rows by one expert matrix.  A call's FLOPs and bytes are counted from the
cell's shapes by the configuration's architecture description
(``bench/archs``, ``call_work``) on the routed rows, not the ``E * C``
capacity rows the static-capacity kernel computes, so a kernel that
skips the padding reads the same work, and today's kernel reads at most
``T * k / (E * C)`` (1 / capacity factor).
"""

import re

from bench import arch
from bench.cells import peaks
from bench.trace import roofline_share

# the jitted wrapper that launches the kernel (repro.kernels.ops)
NAME = "_gmm_jit"
KERNEL = re.compile(rf"^{NAME}\b")


def call_work(config, traffic):
    """(FLOPs, bytes) of one call of the kernel in this cell, on one chip."""
    return arch.of(config).call_work(NAME, config, traffic)


def compute(trace, ctx):
    events = trace.events(lambda n: bool(KERNEL.search(n)))
    if not any(events.values()):
        return None
    f, b = call_work(ctx["config"], ctx["traffic"])
    return roofline_share(events, f, b, peaks(ctx["kind"]))
