"""Device milliseconds per tick in the decide's ``drs.gates`` scope: the
improvement and cost gates, action selection and the applied allocation."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("gates",))
