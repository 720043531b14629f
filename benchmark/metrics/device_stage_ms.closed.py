"""Engines and models: round-weighted mean of the device stage (issue to fenced readback, host clock)."""

from benchmark import layers


def read(ctx):
    return layers.stage_ms(ctx, ("device",))
