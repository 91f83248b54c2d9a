"""Property tests for the Pallas field kernels (hypothesis).

hypothesis is an optional dev dependency (DESIGN.md §8): this module skips
cleanly when it is absent; deterministic fallbacks live in test_kernels.py.
"""
import pytest

pytest.importorskip("hypothesis")

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import field
from repro.kernels import modmatmul
from conftest import exact_modmatmul


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 80), k=st.integers(1, 120), n=st.integers(1, 60),
       seed=st.integers(0, 2 ** 20))
def test_modmatmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, field.P, (m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(0, field.P, (k, n)), jnp.int32)
    got = np.asarray(modmatmul.modmatmul(a, b)).astype(object)
    assert (got == exact_modmatmul(a, b, field.P)).all()
