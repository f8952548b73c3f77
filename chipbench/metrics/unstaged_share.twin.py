"""Share (%) of the chip's busy time in device ops that no stage scope of
the program covers."""

from chipbench import stage_time


def read(ctx):
    return stage_time.unstaged_share(ctx)
