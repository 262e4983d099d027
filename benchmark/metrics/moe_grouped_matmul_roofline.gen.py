"""Least time for the calls of the kernel named ``moe_grouped_matmul`` inside the DECODE programs (the routed
rows' flops against, every step and expert layer, the weights of the experts a step of 64 rows is expected
to touch UNDER EVEN ROUTING: the family's ``kernel_work``; a skewed router touches fewer and the share then
reads higher than the kernel earns) over the kernel's device time there. The prefills' calls of the kernel
get no roofline: ``measure.kernel_roofline`` hands a family one name and one count."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'moe_grouped_matmul', within='decode')
