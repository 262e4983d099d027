"""Least time for the calls of the kernel named ``flash_prefill`` inside the prefill programs (the family's
ideal work at keys 192 / values 128 wide) over the kernel's device time there."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'flash_prefill', within='prefill')
