"""Device milliseconds per simulated control window in the fused loop:
the summed device time of the loops that launch the ``queue_step``
kernel (each window's scan over its steps), over the traced windows."""

KERNEL = "queue_step"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    loops = trace.enclosing(KERNEL)
    if not loops:
        return None
    windows = ctx["result"]["attempted"] * ctx["traffic"]["ticks"]
    return 1e3 * sum((e - s) * 1e-9 for _, s, e in loops) / windows
