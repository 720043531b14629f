"""Device: share of the traced window in which no operation ran on the chip, from the profiler trace."""

from benchmark import layers


def read(ctx):
    return layers.idle_share(ctx)
