"""Least time for the calls of the kernel named ``flash_decode`` inside the decode-chunk programs (the larger
of the family's flops over the bf16 peak and bytes over the HBM peak) over the kernel's device time there."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'flash_decode', within='decode_chunk')
