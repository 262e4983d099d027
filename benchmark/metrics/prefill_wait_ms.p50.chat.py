"""A prefill's jit call on the host to its program's start on the device: the time behind the chunk in flight."""

from benchmark import spans


def read(ctx):
    return spans.part_p50(ctx, 'wait')
