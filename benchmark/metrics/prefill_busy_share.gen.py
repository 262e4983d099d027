"""Device time of programs named as prefills over the device's busy time."""

from benchmark import spans


def read(ctx):
    return spans.prefill_busy_share(ctx)
