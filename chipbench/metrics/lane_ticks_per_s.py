"""Lanes times simulated control windows completed, over the time from
the window's start to the last call's outputs on the host."""

from chipbench import bench


def read(ctx):
    r = ctx["result"]
    if not r.get("lane_ticks"):
        return None
    return bench.rate(r["lane_ticks"], r["elapsed_s"])
