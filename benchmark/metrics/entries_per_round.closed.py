"""Dispatcher: entries per dispatch round closed in the window."""

from benchmark import layers


def read(ctx):
    return layers.per_round(ctx, "entries")
