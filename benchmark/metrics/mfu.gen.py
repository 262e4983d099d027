"""Whole step: model flops of the traced window's work (a token's own 4 experts of the 64, attention over
its context in the 2 attention layers of 10) over wall seconds x bf16 peak."""

from benchmark import measure


def read(ctx):
    return measure.mfu(ctx)
