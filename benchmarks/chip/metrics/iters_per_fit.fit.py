"""Mean BMRM iterations per model finished in the window, from the
estimator's own report (`FitReport.iterations`; a path's lambdas count
one model each)."""


def read(ctx):
    iters = ctx.window.iters_per_model
    return sum(iters) / len(iters) if iters else None
