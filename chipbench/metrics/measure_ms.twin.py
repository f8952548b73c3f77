"""Device milliseconds per simulated control window in the fused loop's
``drs.measure`` scope: the window's measurement and the run aggregates."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_window_ms(ctx, ("measure",))
