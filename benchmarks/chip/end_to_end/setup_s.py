"""Seconds from the start of the cell's set-up (data, oracle, warm-up
and any compile) to the start of the window."""


def read(ctx):
    return ctx.setup_s
