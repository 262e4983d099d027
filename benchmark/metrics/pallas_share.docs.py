"""Device time inside Pallas kernels (``flash_prefill``, ``mla_decode``, ``moe_grouped_matmul``) over device busy time."""

from benchmark import measure


def read(ctx):
    return measure.pallas_share(ctx)
