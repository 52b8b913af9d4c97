"""Device ms per unit of the bundle QP, the iterate and the gap: the
operations under the program's 'qp' scope in the traced sample
(scopes.py)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, 'qp')
