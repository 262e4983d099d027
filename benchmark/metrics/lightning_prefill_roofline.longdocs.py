"""Least time for the calls of the kernel named ``lightning_prefill`` inside the prefill programs (q, k, v in and
o out once a lightning layer, against the recurrence's flops: the larger of the family's flops over the bf16 peak
and bytes over the HBM peak) over the kernel's device time there. The kernel computes chunk-wise, more flops than
the recurrence needs, and window padding is its own: both read as a lower share."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'lightning_prefill', within='prefill')
