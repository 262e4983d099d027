"""Engine's first token to the first frame at the client."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.spans_ms(ctx, 'engine_first', 'first_frame'), 50)
