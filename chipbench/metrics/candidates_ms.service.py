"""Device milliseconds per tick in the decide's ``drs.candidates`` scope:
the budget and the gather of each lane's candidate gains."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("candidates",))
