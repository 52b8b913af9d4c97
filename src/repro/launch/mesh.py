"""Production mesh construction (assignment §MULTI-POD DRY-RUN).

A FUNCTION, not a module constant: importing this module never touches jax
device state. Single pod: (16, 16) = 256 chips ('data', 'model'); multi-pod:
(2, 16, 16) = 512 chips ('pod', 'data', 'model').

Every axis is `AxisType.Auto`: the sharded oracle bodies place their
arrays with `with_sharding_constraint`, which the `Explicit` axes that
`jax.make_mesh` gives by default reject.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh with `Auto` axes, e.g. (4, 1) ('data', 'model')
    over the four chips of one host."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
