"""engine.generate's submit to the slot assigned, requests submitted in the window (the engine's ring of finished legs)."""

from benchmark import spans


def read(ctx):
    return spans.queue_wait_p50(ctx)
