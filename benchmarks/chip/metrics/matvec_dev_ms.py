"""Device ms per unit of the score matvec: the operations under the
program's 'matvec' scope in the traced sample (scopes.py)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, 'matvec')
