"""Device time inside Pallas kernels over device busy time."""

from benchmark import measure


def read(ctx):
    return measure.pallas_share(ctx)
