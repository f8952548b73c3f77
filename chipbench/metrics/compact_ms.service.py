"""Device milliseconds per tick in the compacted decide's ``drs.compact``
scope: trigger scan, changed test, and the gather and scatter of the lanes."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("compact",))
