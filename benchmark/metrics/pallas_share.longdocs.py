"""Device time inside Pallas kernels (``lightning_prefill``, ``lightning_decode``, ``sparse_block_prefill``,
``sparse_block_decode``) over device busy time."""

from benchmark import measure


def read(ctx):
    return measure.pallas_share(ctx)
