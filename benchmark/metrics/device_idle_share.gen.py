"""1 - union of device-op intervals over the traced window."""

from benchmark import measure


def read(ctx):
    return measure.device_idle_share(ctx)
