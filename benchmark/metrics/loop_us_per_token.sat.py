"""Microseconds of the event loop's loop.deliver spans a token they carried."""

from benchmark import loop_spans


def read(ctx):
    return loop_spans.loop_us_per_token(ctx)
