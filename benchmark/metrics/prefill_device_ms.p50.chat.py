"""Device time of the prefill program that carried the request."""

from benchmark import spans


def read(ctx):
    return spans.part_p50(ctx, 'device')
