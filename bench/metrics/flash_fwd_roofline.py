"""flash_fwd_roofline: the flash attention forward kernel's share of its
roofline, in %.

Each call's least time is the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth; the share is the calls' least time over
the sum of their device durations.  Work is counted from the call's
shapes (the local microbatch ``b``, sequence ``s``, the device's ``n_h``
heads of ``d_qk = d_v = d_head``) as causal work,
``2 * b * n_h * (s**2 / 2) * (d_qk + d_v)`` FLOPs, and q, k, v read and o
written once in bf16.
"""

import re

from bench.cells import peaks
from bench.trace import roofline_share
from bench.weights import dims_of

# the jitted wrapper that launches the kernel (repro.kernels.ops)
KERNEL = re.compile(r"^_flash_attention_jit\b")


def call_work(config, traffic):
    """(FLOPs, bytes) of one call of the kernel in this cell."""
    d = dims_of(config)
    pp, dp, tp = config["parallel"]["mesh"]
    b = int(traffic["global_batch"]) // int(traffic["n_micro"]) // dp
    s = int(traffic["seq_len"])
    n_h = d.n_h // tp
    dq = dv = d.d_head
    flops = 2.0 * b * n_h * (s * s / 2) * (dq + dv)
    nbytes = 2.0 * b * s * n_h * (dq + dq + dv + dv)
    return flops, nbytes


def compute(trace, ctx):
    events = trace.events(lambda n: bool(KERNEL.search(n)))
    if not any(events.values()):
        return None
    f, b = call_work(ctx["config"], ctx["traffic"])
    return roofline_share(events, f, b, peaks(ctx["kind"]))
