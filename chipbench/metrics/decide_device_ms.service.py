"""Device-busy milliseconds per tick of the decide service: the union of
device operations over the traced window, divided by its ticks."""


def read(ctx):
    trace, ticks = ctx["trace"], ctx["result"].get("attempted")
    if trace is None or not trace.ops or not ticks:
        return None
    return 1e3 * trace.busy_s / ticks
