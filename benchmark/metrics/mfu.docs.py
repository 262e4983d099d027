"""Whole step: model flops of the traced window's work (a token's own experts among the held, attention in
its plain form) over wall seconds x bf16 peak."""

from benchmark import measure


def read(ctx):
    return measure.mfu(ctx)
