"""Device milliseconds per tick in the decide's ``drs.price`` scope: the
E[T] gathers at the current and the proposed allocation."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("price",))
