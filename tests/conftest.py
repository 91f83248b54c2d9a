"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see 1 CPU device;
multi-device behaviour is tested via subprocesses (test_distributed.py)."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


def exact_modmatmul(a, b, p):
    """Python-int (object dtype) oracle — immune to int64 overflow."""
    ao = np.asarray(a).astype(object)
    bo = np.asarray(b).astype(object)
    return (ao @ bo) % p


@pytest.fixture()
def worker_path(request, monkeypatch):
    """The worker step's path, forced for one test (indirect parameter:
    "fused" or "reference"; None leaves it to the platform, as everywhere
    else).  Jit caches are cleared on entry and exit, so a program traced
    under one path is never served to the other."""
    from repro.core.protocol import compute
    path = request.param
    assert path in (None, "fused", "reference"), path
    jax.clear_caches()
    if path is not None:
        monkeypatch.setattr(compute, "fused", lambda: path == "fused")
    yield path
    jax.clear_caches()
