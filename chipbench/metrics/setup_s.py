"""Seconds from the process's start to the end of warm-up: imports,
device, fleet and traffic, compilation (or cache loads), warm-up ticks."""


def read(ctx):
    return ctx["setup_s"]
