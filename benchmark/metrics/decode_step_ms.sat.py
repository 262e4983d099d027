"""Device time of decode programs in the traced window over the decode steps run there."""

from benchmark import measure


def read(ctx):
    return measure.decode_step_ms(ctx)
