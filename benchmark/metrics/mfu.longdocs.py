"""Whole step: model flops of the traced window's work (the kept blocks' keys, the compressed keys scored, the
recurrence's state update and read-out, every matmul) over wall seconds x bf16 peak."""

from benchmark import measure


def read(ctx):
    return measure.mfu(ctx)
