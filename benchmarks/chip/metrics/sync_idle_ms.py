"""Idle device ms per unit while the host reads a chunk's results: idle
time under the program's 'bmrm.sync' span in the traced sample
(scopes.py). Serves `sync_idle_ms.<cell kind>` in every cell."""

import scopes


def read(ctx):
    return scopes.idle_ms(ctx, 'bmrm.sync')
