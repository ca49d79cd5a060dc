"""step_mfu: the whole step's share of the chips' bf16 peak, in %.

Model FLOPs of the steps in the traced window over (chips x peak x the
window's length).  The FLOPs per token are the configuration's
architecture description's (``bench/archs``, ``flops_per_token``): 6 x
the active matmul parameters plus causal attention.  Recomputation and
capacity padding do not count.
"""

from bench import arch
from bench.cells import peaks


def flops_per_token(config, seq_len: int) -> float:
    return arch.of(config).flops_per_token(config, seq_len)


def compute(trace, ctx):
    if not trace.devices:
        return None
    tr = ctx["traffic"]
    s = int(tr["seq_len"])
    tokens = ctx["steps"] * int(tr["global_batch"]) * s
    peak = peaks(ctx["kind"])["bf16_flops_per_s"]
    return 100.0 * tokens * flops_per_token(ctx["config"], s) / (
        ctx["chips"] * peak * trace.window_s())
