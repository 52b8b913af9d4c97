"""Device ms per unit of the counting pass and the loss: the operations
under the program's 'counts' scope, its 'sort', 'tree', 'query' and
'unsort' scopes included, in the traced sample (scopes.py). Serves
`count_dev_ms.<cell kind>` in every cell."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, 'counts')
