"""Least time for the calls of the kernel named ``sparse_block_prefill`` inside the prefill programs (QK^T and PV
over the KEPT keys of every prompt token, K and V once a sparse layer) over the kernel's device time there; the
kernel computes whole tiles under the mask, so what the selection drops reads as a lower share."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'sparse_block_prefill', within='prefill')
