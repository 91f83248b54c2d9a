"""Faults planted in the program under test, to show that ``correct`` fails.

Each is a context manager that breaks the timed path underneath a run and
restores it after. Both drivers reach the planted code: ``protocol.train``
and ``ClusterRunner`` both set up through ``engine.setup`` and step through
``engine._gradient_step``, and the shard backend gathers through
``jax.lax.all_gather``. JAX's in-memory caches are cleared on the way in and
out, so no program traced before the fault (or with it) is reused.

  unchanged  a step that returns its state unchanged
  half       half of the rows left out, the mean taken over the rest
  altered    one weight negated where the step produces it
  exchange   the all_gather between chips left out: each chip decodes from
             copies of its own results
"""
from __future__ import annotations

import contextlib

from bench import program  # noqa: F401  (puts the program on sys.path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.protocol import engine  # noqa: E402


@contextlib.contextmanager
def _patched(owner, name: str, replacement):
    original = getattr(owner, name)
    jax.clear_caches()
    setattr(owner, name, replacement(original))
    try:
        yield
    finally:
        setattr(owner, name, original)
        jax.clear_caches()


def _unchanged(step):
    def gradient_step(cfg, w2, *args, **kwargs):
        return w2
    return gradient_step


def _half(setup):
    def half_setup(cfg, key, x, y, *args, **kwargs):
        return setup(cfg, key, x[::2], y[::2], *args, **kwargs)
    return half_setup


def _altered(step):
    def gradient_step(*args, **kwargs):
        w2 = step(*args, **kwargs)
        return w2.at[0].set(-w2[0])
    return gradient_step


def _exchange(all_gather):
    def local_only(x, axis_name, *, axis=0, tiled=False, **kwargs):
        n = jax.sharding.get_abstract_mesh().shape[axis_name]
        return jnp.concatenate([x] * n, axis=axis)
    return local_only


FAULTS = {
    "unchanged": (engine, "_gradient_step", _unchanged),
    "half": (engine, "setup", _half),
    "altered": (engine, "_gradient_step", _altered),
    "exchange": (jax.lax, "all_gather", _exchange),
}


def planted(name: str):
    owner, attr, replacement = FAULTS[name]
    return _patched(owner, attr, replacement)
