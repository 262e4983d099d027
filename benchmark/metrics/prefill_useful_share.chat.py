"""Prompt tokens over prompt tokens plus bucket padding."""

from benchmark import measure


def read(ctx):
    return measure.prefill_useful_share(ctx)
