"""Device idle covered by no engine span, by the blocking wait for work, or shorter than 50 us: what the spans do not explain."""

from benchmark import spans


def read(ctx):
    return spans.idle_share(ctx, 'unspanned')
