"""A first token's loop.deliver to its first frame built (loop.gateway_out, index 0), from inside."""

from benchmark import loop_spans


def read(ctx):
    return loop_spans.deliver_to_frame_p50(ctx)
