"""Mean share (%) of lanes the compacted decide repriced per tick (the
``repriced`` mask it returns), over the window's ticks."""


def read(ctx):
    shares = ctx["result"].get("repriced_share")
    return 100.0 * sum(shares) / len(shares) if shares else None
