"""Least time for the calls of the kernel named ``mla_decode`` inside the decode-chunk programs (the latent
read once a layer a step: the larger of the family's flops over the bf16 peak and bytes over the HBM peak)
over the kernel's device time there."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'mla_decode', within='decode_chunk')
