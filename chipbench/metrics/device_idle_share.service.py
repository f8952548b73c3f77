"""Share (%) of the traced window in which no operation ran on the chip,
under the decide service's host loop."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None or not trace.ops else 100.0 * trace.idle_share()
