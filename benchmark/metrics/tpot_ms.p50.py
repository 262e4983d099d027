"""Median over requests of (last frame - first frame) / (output tokens - 1)."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.tpot_ms(ctx), 50)
