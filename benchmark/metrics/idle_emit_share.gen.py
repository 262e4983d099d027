"""Share of the traced window in which the device runs nothing and the engine thread is in its emit span."""

from benchmark import spans


def read(ctx):
    return spans.idle_share(ctx, 'emit')
