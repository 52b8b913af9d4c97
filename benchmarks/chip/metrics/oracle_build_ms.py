"""Host ms per unit building the oracle inside `RankSVM.fit`: the
program's 'ranksvm.make_oracle' span in the traced sample (scopes.py)."""

import scopes


def read(ctx):
    return scopes.span_ms(ctx, 'ranksvm.make_oracle')
