"""Dispatcher: round-weighted mean wait from wire admission to queue pop, over the rounds the window closed."""

from benchmark import layers


def read(ctx):
    return layers.stage_ms(ctx, ("queue",))
