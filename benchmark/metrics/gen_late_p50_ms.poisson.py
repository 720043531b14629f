"""Load generator: median of how late pushes left the shim against their schedule."""

from benchmark import layers


def read(ctx):
    return layers.summary(ctx, "gen_late_p50_ms")
