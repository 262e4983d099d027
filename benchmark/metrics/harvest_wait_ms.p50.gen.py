"""A prefill program's end on the device to the host's harvest of its first token: the chunk a slot sits out."""

from benchmark import spans


def read(ctx):
    return spans.part_p50(ctx, 'harvest')
