"""Shared helper: assert both counts implementations (the searched tree
the sharded oracle runs, and `counts_fused`) match the O(m^2) reference
bit-for-bit. Imported by test_counts.py and test_properties.py so the
parity invariant is defined once."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import counts as C
from repro.core import ref as R


@jax.jit
def searched_counts(p, y):
    """(c, d) by binary searches of the merge-sort tree, d by reflection:
    the sharded oracle's counting (core.distributed), unsharded."""
    return C._half_counts(p, y), C._half_counts(-p, -y)


def assert_counts_match(p, y):
    c, d = searched_counts(jnp.asarray(p), jnp.asarray(y))
    cr, dr = R.counts_ref(jnp.asarray(p), jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
    # the fast path every other caller runs must agree bit-for-bit too
    cf, df = C.counts_fused(jnp.asarray(p), jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(cf), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(df), np.asarray(dr))
    return np.asarray(cf), np.asarray(df)
