"""Device milliseconds per tick in the decide's ``drs.trigger`` scope: the
overload trigger and the capped propagation loop."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("trigger",))
