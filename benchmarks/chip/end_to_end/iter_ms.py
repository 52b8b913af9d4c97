"""Milliseconds of the window per BMRM iteration completed in it."""


def read(ctx):
    return 1e3 * ctx.window.seconds / ctx.window.counts['iterations']
