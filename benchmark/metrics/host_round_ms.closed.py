"""Round paths: round-weighted mean host time of a round from queue pop to the wire (batch_form + reasm + device_submit + drain + send)."""

from benchmark import layers


def read(ctx):
    return layers.stage_ms(ctx, layers.HOST_STAGES)
