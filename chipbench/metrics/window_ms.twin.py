"""Device milliseconds per simulated control window in the fused loop's
``drs.window`` scope: capacities and the window's scan over its steps."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_window_ms(ctx, ("window",))
