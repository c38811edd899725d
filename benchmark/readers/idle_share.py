"""The device's idle share of the traced span, in percent, from the
trace's reduction (``trace_reduce.reduce``).  No trace, or a trace in
which no operation ran on a device: nothing."""


def read(spec: dict, ctx: dict):
    t = ctx.get("trace")
    if not t:
        return None
    return 100.0 * t["idle_share"]
