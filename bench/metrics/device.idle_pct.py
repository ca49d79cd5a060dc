"""device.idle_pct: the share of the traced window in which no operation
runs on the device, in %; on several chips, the idlest one's."""


def compute(trace, ctx):
    if not trace.devices:
        return None
    return 100.0 * max(trace.idle_share(d) for d in trace.devices)
