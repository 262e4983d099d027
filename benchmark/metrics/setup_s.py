"""Process start to the window's first instant."""


def read(ctx):
    return ctx['setup_s']
