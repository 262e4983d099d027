"""Client send to engine.generate: gateway, runner, broker, agent."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.spans_ms(ctx, 'sent', 'engine_submit'), 50)
