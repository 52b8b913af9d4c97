"""One oracle step, called from outside at the last iterate of the
window's fit: score matvec, counting pass, loss and subgradient transpose
product (`RankOracle.loss_and_subgrad`), median ms per call. Serves
`oracle_ms.<cell kind>` in every cell."""


def read(ctx):
    oracle, w = ctx.job.oracle, ctx.job.w
    if oracle is None or w is None:
        return None
    return ctx.time_ms(lambda: oracle.loss_and_subgrad(w))
