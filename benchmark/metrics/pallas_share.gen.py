"""Device time inside Pallas kernels (``moe_grouped_matmul``, ``flash_decode``) over device busy time."""

from benchmark import measure


def read(ctx):
    return measure.pallas_share(ctx)
