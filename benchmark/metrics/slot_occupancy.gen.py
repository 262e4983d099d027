"""Useful decode tokens over decode steps x slots."""

from benchmark import measure


def read(ctx):
    return measure.slot_occupancy(ctx)
