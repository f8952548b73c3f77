"""Device milliseconds per tick in the decide's ``drs.solve`` scope:
offered-load clamping, the inflow rescale and the batched Jackson solve."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("solve",))
