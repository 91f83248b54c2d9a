"""Compute stage: the worker-side polynomial f (paper Eq. 20), per backend.

f(X̃, W̃) = X̃ᵀ ḡ(X̃, W̃) over F_p — degree (2r+1) in the encoding variable,
so any (2r+1)(K+T-1)+1 surviving workers decode (Thm. 1).  The multi-head
generalization stacks c one-vs-all polynomials over the SAME share:
W̃ (d, c, r) -> result (d, c); the dominant X̃ read is amortized across heads.

Backend matrix (DESIGN.md §4):
  * "vmap"     — all N workers simulated on one device (tests/benchmarks).
  * "shard"    — shard_map over the active mesh's worker axis: each of
                 the D devices evaluates its block of N/D coded shares
                 (D must divide N) with zero collectives in the worker step
                 (the paper's key property), then one all_gather plays
                 "send results to master".
  * the path  — on a TPU the fused Pallas kernel (kernels/coded_grad.py)
                 takes every worker's share in one call on either backend;
                 elsewhere the jnp reference (kernels/ref.py), vmapped.
                 The platform picks it at trace time (``fused``).
"""
from __future__ import annotations

from typing import Callable

import jax

from repro.core import field
from repro.core.protocol.config import CPMLConfig
from repro.obs.metrics import REGISTRY


def fused() -> bool:
    """Whether the worker step runs the fused kernel: whether JAX's default
    backend is a TPU."""
    return jax.default_backend() == "tpu"


def _count(cfg: CPMLConfig, rows: int) -> None:
    """One more worker step built, labelled with its path, its digits or
    limbs and the rows of a share it reads at a time."""
    REGISTRY.counter("cpml_worker_programs",
                     "worker steps built, by path",
                     path="fused" if fused() else "reference",
                     nl=field.n_limbs(cfg.p), rows=rows).inc()


def workers_fn(cfg: CPMLConfig, cbar: jax.Array
               ) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """f(X̃, W̃) for a stack of workers: (n, mk, d), (n, d, c, r) ->
    (n, d, c)."""

    def f(x_shares: jax.Array, w_shares: jax.Array) -> jax.Array:
        mk, d = x_shares.shape[1:]
        if fused():
            from repro.kernels import coded_grad
            _count(cfg, coded_grad.row_block(mk, d))
            return coded_grad.coded_grad_workers(x_shares, w_shares, cbar,
                                                 cfg.p)
        # the unfused jnp path IS the kernel oracle (itself pinned to a
        # python-int ground truth in test_kernels.py)
        from repro.kernels import ref
        _count(cfg, mk)
        return jax.vmap(lambda x, w: ref.coded_grad_mc_ref(x, w, cbar, cfg.p)
                        )(x_shares, w_shares)

    return f


def worker_fn(cfg: CPMLConfig, cbar: jax.Array
              ) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """f(X̃, W̃) for ONE worker. (mk, d), (d, c, r) -> (d, c)."""
    fs = workers_fn(cfg, cbar)

    def f(x_share: jax.Array, w_share: jax.Array) -> jax.Array:
        return fs(x_share[None], w_share[None])[0]

    return f


def shard_devices(cfg: CPMLConfig) -> int | None:
    """Devices D on the active mesh's worker axis under backend='shard',
    each holding a block of N/D shares; None for vmap or with no such mesh.
    """
    if cfg.backend != "shard":
        return None
    axis = cfg.mesh_axis
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axis not in mesh.axis_names:
        return None
    n_dev = mesh.shape[axis]
    if cfg.N % n_dev:
        raise ValueError(
            f"backend='shard': {n_dev} devices on mesh axis {axis!r} do "
            f"not divide N={cfg.N} workers; each device evaluates an "
            f"equal block of N/D shares")
    return n_dev


def all_worker_results(cfg: CPMLConfig, cbar: jax.Array, x_shares: jax.Array,
                       w_shares: jax.Array) -> jax.Array:
    """(N, mk, d) x (N, d, c, r) -> (N, d, c) worker results.

    Under ``shard`` the dataset shares arrive as the sharded encode left
    them, N/D on each device (encode.py), and enter shard_map as they are.
    """
    f = workers_fn(cfg, cbar)
    if cfg.backend == "vmap":
        return f(x_shares, w_shares)
    elif cfg.backend == "shard":
        from jax.sharding import PartitionSpec as Pspec
        axis = cfg.mesh_axis
        if shard_devices(cfg) is None:
            raise ValueError(
                f"backend='shard' needs an active mesh with a {axis!r} axis: "
                f"run under `with jax.set_mesh(mesh):`")

        def shard_body(xs, ws):
            # this device's block of N/D workers, with no collective
            res = f(xs, ws)
            # "send result back to the master": one collective, results
            # replicated so the (replicated) decode can run everywhere.
            return jax.lax.all_gather(res, axis, axis=0, tiled=True)

        # check_vma=False: the all_gather makes the output replicated, but
        # the static replication check cannot infer that.
        return jax.shard_map(shard_body, in_specs=(Pspec(axis), Pspec(axis)),
                             out_specs=Pspec(), check_vma=False
                             )(x_shares, w_shares)
    raise ValueError(cfg.backend)
