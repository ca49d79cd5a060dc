"""step_mfu: the whole step's share of the chips' bf16 peak, in %.

Model FLOPs of the steps in the traced window over (chips x peak x the
window's length).  Per token: 6 x the active matmul parameters (the
attention projections, the dense MLP or the router and ``k`` of the ``E``
experts, and the output head; not the embedding lookup) plus causal
attention, ``6 * layers * (s / 2) * heads * (d_qk + d_v)``.  Recomputation
and capacity padding do not count.
"""

from bench.cells import peaks
from bench.weights import dims_of


def flops_per_token(config, seq_len: int) -> float:
    d = dims_of(config)
    attn = d.h * d.n_h * d.d_head + 2 * d.h * d.n_kv * d.d_head \
        + d.n_h * d.d_head * d.h
    ffn = (d.top_k * 3 * d.h * d.expert_ff + d.h * d.experts) if d.moe \
        else 3 * d.h * d.ff
    active = d.layers * (attn + ffn) + d.h * d.vocab
    return 6.0 * active + 6.0 * d.layers * (seq_len / 2) * d.n_h \
        * (2 * d.d_head)


def compute(trace, ctx):
    if not trace.devices:
        return None
    tr = ctx["traffic"]
    s = int(tr["seq_len"])
    tokens = ctx["steps"] * int(tr["global_batch"]) * s
    peak = peaks(ctx["kind"])["bf16_flops_per_s"]
    return 100.0 * tokens * flops_per_token(ctx["config"], s) / (
        ctx["chips"] * peak * trace.window_s())
