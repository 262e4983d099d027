"""Least time for the decode-attention kernel's calls over its device time."""

from benchmark import measure


def read(ctx):
    return measure.decode_attn_roofline(ctx)
