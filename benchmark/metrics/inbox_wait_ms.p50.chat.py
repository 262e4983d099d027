"""A first token's harvest on the engine thread to the start of its loop.deliver: its wait for the loop's thread (inbox, GIL)."""

from benchmark import loop_spans


def read(ctx):
    return loop_spans.inbox_wait_p50(ctx)
