"""moe.dropped_pct: the share of the step's routed (token, expert)
assignments that reached no expert, in %: ``100 * (1 - moe_kept /
moe_routed)``, from the counters the step returns in its metrics
(``moe_routed``, ``moe_kept``: whole-step counts of the forward ticks;
under expert parallelism an assignment is kept only past both the send
bucket and the receiving rank's capacity).  Read from
``ctx["counters"]`` (the last traced step's); nothing where it is absent
or nothing was routed (a dense model)."""


def compute(trace, ctx):
    c = ctx.get("counters") or {}
    routed = c.get("moe_routed", 0)
    if not routed:
        return None
    return 100.0 * (1.0 - c["moe_kept"] / routed)
