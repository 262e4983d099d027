"""Least time for the calls of the kernel named ``lightning_decode`` inside the decode-chunk programs (the float32
state read once and written once a lightning layer a token) over the kernel's device time there; slots that ride
along are read and written too and read as a lower share."""

from benchmark import measure


def read(ctx):
    return measure.kernel_roofline(ctx, 'lightning_decode', within='decode_chunk')
