"""Device milliseconds per simulated control window in the decide core's
stages (``repro.core.stages.DECIDE``) inside the fused loop."""

from chipbench import stage_time

STAGES = ("trigger", "solve", "table", "candidates", "topr", "price", "gates")


def read(ctx):
    return stage_time.per_window_ms(ctx, STAGES)
