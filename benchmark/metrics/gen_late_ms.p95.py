"""Load generator: send instant minus due instant."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.spans_ms(ctx, 'due', 'sent'), 95)
