"""Median tick latency (ms) over every tick of the window."""

from chipbench import bench


def read(ctx):
    ticks = ctx["result"].get("tick_s")
    return bench.percentile(ticks, 50) * 1e3 if ticks else None
