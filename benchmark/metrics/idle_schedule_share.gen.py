"""The same for admission, batch and dispatch building, linger and the host's part of a harvest or a wait."""

from benchmark import spans


def read(ctx):
    return spans.idle_share(ctx, 'schedule')
