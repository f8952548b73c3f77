"""Mean share (%) of keyed operator-lanes per tick whose least stable
allocation lies above the pooled M/M/k floor ``floor(lam/mu) + 1`` (the
keyed decide's ``hot_floor`` output), over the window's ticks."""


def read(ctx):
    shares = ctx["result"].get("hot_floor_share")
    return sum(shares) / len(shares) if shares else None
