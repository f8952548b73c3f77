"""Device milliseconds per tick in the decide's ``drs.table`` scope: the
Erlang sojourn table, the gain table and the minimal feasible allocation."""

from chipbench import stage_time


def read(ctx):
    return stage_time.per_tick_ms(ctx, ("table",))
