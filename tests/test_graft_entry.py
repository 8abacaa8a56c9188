"""The driver contract: entry() compiles, dryrun_multichip() runs a step.

These are the integration points an external harness exercises; breaking
them silently would cost a whole round.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def test_entry_compiles():
    import __graft_entry__ as g

    fn, args = g.entry()
    loss = jax.jit(fn)(*args)
    assert bool(jnp.isfinite(loss))


@pytest.mark.slow
def test_dryrun_multichip_8():
    import __graft_entry__ as g

    g.dryrun_multichip(8)  # raises on any failure
