"""Seeded inputs for the benchmark, made on the device in one jitted call.

The generators are copies of the MNIST stand-ins the program ships
(``repro.data.synthetic``): sparse pixel-like features in [0, 1] with a
planted linear separator. The benchmark owns its copy so that no later
change to the program can change the inputs it is measured on.

Every random stream of a run derives from ``--seed`` through ``base_key``:
the data from stream 0, the training jobs from stream 1, the cluster run
from stream 2, the layer probes of a traced run from stream 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DATA, JOBS, CLUSTER, PROBES = 0, 1, 2, 3


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits used.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a Python int when
    64-bit mode is off, so the high bits are folded in on their own.
    """
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


def stream(seed: int, which: int) -> jax.Array:
    return jax.random.fold_in(base_key(seed), which)


@functools.partial(jax.jit, static_argnames=("m", "d", "sparsity", "margin"))
def mnist_like(key, m: int, d: int, sparsity: float, margin: float):
    """Binary task: (x (m, d) float32 in [0, 1], y (m,) float32 0/1)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.uniform(k1, (m, d))
    mask = jax.random.uniform(k2, (m, d)) > sparsity
    x = jnp.where(mask, x, 0.0)
    w_true = jax.random.normal(k3, (d,)) / np.sqrt(d)
    logits = margin * (x @ w_true)
    logits = logits - jnp.median(logits)
    y = (jax.random.uniform(k4, (m,)) < jax.nn.sigmoid(logits)).astype(
        jnp.float32)
    return x, y


@functools.partial(jax.jit,
                   static_argnames=("m", "d", "c", "sparsity", "margin"))
def multiclass_mnist_like(key, m: int, d: int, c: int, sparsity: float,
                          margin: float):
    """c-class task: (x (m, d) float32 in [0, 1], labels (m,) int32)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.uniform(k1, (m, d))
    mask = jax.random.uniform(k2, (m, d)) > sparsity
    x = jnp.where(mask, x, 0.0)
    w_true = jax.random.normal(k3, (d, c)) / np.sqrt(d)
    logits = margin * (x @ w_true)
    labels = jax.random.categorical(k4, logits, axis=-1).astype(jnp.int32)
    return x, labels


def make_dataset(config: dict, seed: int):
    """The configuration's dataset from the seed, on the default device."""
    spec = dict(config["data"])
    make = GENERATORS[spec.pop("generator")]
    if make is multiclass_mnist_like:
        spec["c"] = config["c"]
    return jax.block_until_ready(
        make(stream(seed, DATA), m=config["m"], d=config["d"], **spec))


GENERATORS = {"mnist_like": mnist_like,
              "multiclass_mnist_like": multiclass_mnist_like}
