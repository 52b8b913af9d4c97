"""Milliseconds of the window per model fitted to eps in it."""


def read(ctx):
    return 1e3 * ctx.window.seconds / ctx.window.counts['models']
