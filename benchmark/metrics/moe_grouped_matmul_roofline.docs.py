"""Least time for the calls of the kernel named ``moe_grouped_matmul`` inside the prefill programs (the
routed rows' flops against every held expert's weights read once a dispatch) over the kernel's device
time there."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'moe_grouped_matmul', within='prefill')
