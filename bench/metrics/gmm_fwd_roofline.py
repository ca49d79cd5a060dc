"""gmm_fwd_roofline: the grouped expert matmul kernel's share of its
roofline, in %.

Each of the layer's three calls (gate, up, down) multiplies the routed
rows by one expert matrix: counted on the ``T * k`` routed rows of the
microbatch (``T * k / ep`` under expert parallelism), not the ``E * C`` capacity rows the static-capacity kernel
computes, so a kernel that skips the padding reads the same work, and
today's kernel reads at most ``T * k / (E * C)`` (1 / capacity factor).
``2 * T * k * h * f`` FLOPs and, in bf16, the routed rows read and
written once and every expert matrix read once.
"""

import re

from bench.cells import peaks
from bench.trace import roofline_share
from bench.weights import dims_of

# the jitted wrapper that launches the kernel (repro.kernels.ops)
KERNEL = re.compile(r"^_gmm_jit\b")


def call_work(config, traffic):
    """(FLOPs, bytes) of one call of the kernel in this cell, on one chip:
    its data shard's tokens; with expert parallelism its share of the
    experts at their full width and the rows routed to them, else every
    expert with its width split over the model axis."""
    d = dims_of(config)
    pp, dp, tp = config["parallel"]["mesh"]
    ep = int(config["parallel"]["ep"])
    T = int(traffic["global_batch"]) // int(traffic["n_micro"]) // dp \
        * int(traffic["seq_len"])
    if ep > 1:
        rows, f, E = T * d.top_k // ep, d.expert_ff, d.experts // ep
    else:
        rows, f, E = T * d.top_k, d.expert_ff // tp, d.experts
    h = d.h
    flops = 2.0 * rows * h * f
    nbytes = 2.0 * (rows * h + E * h * f + rows * f)
    return flops, nbytes


def compute(trace, ctx):
    events = trace.events(lambda n: bool(KERNEL.search(n)))
    if not any(events.values()):
        return None
    f, b = call_work(ctx["config"], ctx["traffic"])
    return roofline_share(events, f, b, peaks(ctx["kind"]))
