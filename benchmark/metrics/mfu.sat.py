"""Whole step: model flops of the traced window's work over wall seconds x bf16 peak."""

from benchmark import measure


def read(ctx):
    return measure.mfu(ctx)
