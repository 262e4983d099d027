"""Output tokens delivered to clients in the window over its seconds."""

from benchmark import measure


def read(ctx):
    return measure.out_tok_s(ctx)
