"""engine.generate to its first token: admission, batching, prefill, harvest order."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.spans_ms(ctx, 'engine_submit', 'engine_first'), 50)
