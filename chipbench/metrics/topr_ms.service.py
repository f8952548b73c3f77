"""Device milliseconds per tick in the decide's ``drs.topr`` scope: the
``gain_topr`` Program (4) selection, its pad and swap included."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("topr",))
