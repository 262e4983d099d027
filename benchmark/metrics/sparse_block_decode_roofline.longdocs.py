"""Least time for the calls of the kernel named ``sparse_block_decode`` inside the decode-chunk programs (the kept
blocks' K and V rows once a sparse layer a query: a count that does not grow with the context past the dense
switch) over the kernel's device time there; a tile read for a few kept blocks reads as a lower share."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'sparse_block_decode', within='decode_chunk')
