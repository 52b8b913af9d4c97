"""Device ms per unit of the subgradient's transpose product: the
operations under the program's 'rmatvec' scope in the traced sample
(scopes.py)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, 'rmatvec')
