"""The share of the engine thread's admit, dispatch and emit spans it spent off the CPU (the GIL, a blocking call)."""

from benchmark import loop_spans


def read(ctx):
    return loop_spans.schedule_offcpu_share(ctx)
