"""Least time for the calls of the kernel named ``flash_decode`` inside the decode-chunk programs (the K and
V rows of the 2 attention layers that the decode queries saw: the family's ``kernel_work``) over the
kernel's device time there."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'flash_decode', within='decode_chunk')
