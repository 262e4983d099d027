"""A chat frame's loop.gateway_in to its engine.generate, from inside: gateway, questions topic, runner, agent."""

from benchmark import loop_spans


def read(ctx):
    return loop_spans.gateway_to_engine_p50(ctx)
