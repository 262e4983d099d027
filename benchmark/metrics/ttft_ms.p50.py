"""Median over all requests due in the window of first frame minus due instant."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.ttft_ms(ctx), 50)
