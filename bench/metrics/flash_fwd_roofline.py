"""flash_fwd_roofline: the flash attention forward kernel's share of its
roofline, in %.

Each call's least time is the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth; the share is the calls' least time over
the sum of their device durations.  A call's FLOPs and bytes are counted
from the cell's shapes by the configuration's architecture description
(``bench/archs``, ``call_work``): causal work, with q, k, v read and o
written once in bf16.
"""

import re

from bench import arch
from bench.cells import peaks
from bench.trace import roofline_share

# the jitted wrapper that launches the kernel (repro.kernels.ops)
NAME = "_flash_attention_jit"
KERNEL = re.compile(rf"^{NAME}\b")


def call_work(config, traffic):
    """(FLOPs, bytes) of one call of the kernel in this cell."""
    return arch.of(config).call_work(NAME, config, traffic)


def compute(trace, ctx):
    events = trace.events(lambda n: bool(KERNEL.search(n)))
    if not any(events.values()):
        return None
    f, b = call_work(ctx["config"], ctx["traffic"])
    return roofline_share(events, f, b, peaks(ctx["kind"]))
