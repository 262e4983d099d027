"""Device time of programs named as decode chunks over the steps their dispatch spans give them."""

from benchmark import spans


def read(ctx):
    return spans.decode_step(ctx)
