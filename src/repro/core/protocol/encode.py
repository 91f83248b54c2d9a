"""Encode stage: quantize + Lagrange-encode datasets and weights.

Algorithm 1 lines 1-3.  The dataset is encoded ONCE (the paper's one-time
encoding property); weights are re-encoded every round because W changes.
Both are shape-generic: weights may be (d,) binary vectors or (d, c)
one-vs-all matrices — quantization, masking and encoding all act
elementwise/linearly, so the c heads ride through a single encode.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core import field, lagrange, quantize
from repro.core.protocol import compute
from repro.core.protocol.config import CPMLConfig
from repro.obs.metrics import REGISTRY
from repro.obs.trace import phase


def pad_rows(x: jax.Array, K: int) -> jax.Array:
    m = x.shape[0]
    pad = (-m) % K
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], 0)
    return x


# device scope of the dataset encode (jax.named_scope), beside the round's
# scopes in engine: it names every op of the compiled encode in op_name
SCOPE_ENCODE_DATASET = "cpml_encode_dataset"


@functools.partial(jax.jit, static_argnums=(0,))
def _encode_dataset(cfg: CPMLConfig, key: jax.Array, x: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    with jax.named_scope(SCOPE_ENCODE_DATASET):
        xq = quantize.quantize_data(x, cfg.lx, cfg.p)      # (m, d) field
        xq = pad_rows(xq, cfg.K)
        mk = xq.shape[0] // cfg.K
        # no optimization_barrier on the parts: on a v5e one changed the
        # masks' contribution to the shares (DESIGN.md §3)
        parts = xq.reshape(cfg.K, mk, xq.shape[-1])
        masks = lagrange.draw_masks(key, cfg.T, parts.shape[1:], cfg.p)
        return lagrange.encode(cfg.scheme, parts, masks, cfg.p), xq


# Bytes of stacked (K+T)-part int32 rows that one row block of the sharded
# encode reads: its temporaries on each chip are a few times this, whatever
# the dataset's size.
ENCODE_BLOCK_BYTES = 1 << 27


@functools.partial(jax.jit, static_argnums=(0,))
def _quantize_masks(cfg: CPMLConfig, key: jax.Array, x: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """The sharded encode's inputs: the padded field dataset (m_pad, d) and
    the T masks (T, m_pad/K, d), drawn at full shape from the same key as
    _encode_dataset draws them."""
    with jax.named_scope(SCOPE_ENCODE_DATASET):
        # zero rows quantize to zero: padding x first makes one output
        xq = quantize.quantize_data(pad_rows(x, cfg.K), cfg.lx, cfg.p)
        shape = (xq.shape[0] // cfg.K, xq.shape[-1])
        return xq, lagrange.draw_masks(key, cfg.T, shape, cfg.p)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _empty_shares(cfg: CPMLConfig, shape: tuple[int, ...]) -> jax.Array:
    return jax.lax.with_sharding_constraint(
        jnp.zeros(shape, jnp.int32), PartitionSpec(cfg.mesh_axis))


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _encode_block(cfg: CPMLConfig, rows: int, shares: jax.Array,
                  xq: jax.Array, masks: jax.Array, start: jax.Array
                  ) -> jax.Array:
    """Rows [start, start + rows) of every part, encoded into each device's
    own block of N/D shares and written into ``shares`` in place."""
    axis = cfg.mesh_axis
    n = cfg.N // compute.shard_devices(cfg)

    def body(shares, xq, masks, start):
        mk = shares.shape[1]
        # row slices of the (m_pad, d) dataset: splitting it into (K, mk, d)
        # would copy all of it where mk is not a multiple of the row tile
        parts = jnp.stack([jax.lax.dynamic_slice_in_dim(
            xq, k * mk + start, rows, 0) for k in range(cfg.K)])
        z = jax.lax.dynamic_slice_in_dim(masks, start, rows, 1)
        mine = (jax.lax.axis_index(axis), n)
        new = lagrange.encode(cfg.scheme, parts, z, cfg.p, block=mine)
        return jax.lax.dynamic_update_slice_in_dim(shares, new, start, 1)

    with jax.named_scope(SCOPE_ENCODE_DATASET):
        rep = PartitionSpec()
        return jax.shard_map(
            body, in_specs=(PartitionSpec(axis), rep, rep, rep),
            out_specs=PartitionSpec(axis))(shares, xq, masks, start)


def block_rows(cfg: CPMLConfig, mk: int, d: int) -> int:
    """Rows of each part that one block of the sharded encode covers."""
    per_row = (cfg.K + cfg.T) * d * 4
    return max(1, min(mk, ENCODE_BLOCK_BYTES // per_row))


def _encode_dataset_sharded(cfg: CPMLConfig, n_dev: int, key: jax.Array,
                            x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The dataset encode placed by worker: each of the n_dev devices
    computes and keeps only its own N/n_dev shares, row block by row block
    (DESIGN.md §3-§4).  The cleartext and the masks are replicated over
    the mesh, as every master-side array is there; each block reads its
    rows of them.  The last block starts early enough to end at m/K, so
    every block has one shape: rows it repeats are rewritten with the same
    values."""
    xq, masks = _quantize_masks(cfg, key, x)
    mk, d = xq.shape[0] // cfg.K, xq.shape[1]
    rows = block_rows(cfg, mk, d)
    shares = _empty_shares(cfg, (cfg.N, mk, d))
    starts = [min(s, mk - rows) for s in range(0, mk, rows)]
    for start in starts:
        with phase("setup.encode_dataset.block"):
            shares = _encode_block(cfg, rows, shares, xq, masks,
                                   jnp.int32(start))
    _count(len(starts), cfg.N // n_dev * mk * d * 4)
    return shares, xq


def _count(blocks: int, share_bytes: int) -> None:
    REGISTRY.counter("cpml_encode_row_blocks",
                     "row blocks the dataset encodes dispatched").inc(blocks)
    REGISTRY.gauge("cpml_share_bytes_per_chip",
                   "bytes of dataset shares each device holds, last "
                   "encode").set(share_bytes)


def encode_dataset(cfg: CPMLConfig, key: jax.Array, x: jax.Array
                   ) -> tuple[jax.Array, dict[str, Any]]:
    """Returns shares (N, m/K, d) + master-side cleartext context.

    Quantize, pad, split, mask draw and encode run as ONE compiled program
    (``cfg`` static), traced once per (cfg, shapes): each call still draws
    its own masks from ``key`` and encodes its own ``x``.  Under
    ``backend="shard"`` with the mesh's worker axis active, the shares come
    out sharded by worker instead, N/D on each device, encoded in row
    blocks; they are bit-identical to the one-program encode.
    """
    n_dev = compute.shard_devices(cfg)
    if n_dev is None:
        shares, xq = _encode_dataset(cfg, key, x)
        _count(1, shares.size * shares.dtype.itemsize)
    else:
        shares, xq = _encode_dataset_sharded(cfg, n_dev, key, x)
    return shares, {"xq": xq, "m_padded": xq.shape[0]}


def encode_weights(cfg: CPMLConfig, key: jax.Array, w: jax.Array) -> jax.Array:
    """Quantize w (Eq. 9-10) and Lagrange-encode W̄ (Eq. 13-14).

    w: (d,) or (d, c) real weights.  Returns shares (N, *w.shape, r).
    Note v(beta_i) = W̄ for ALL i <= K (the paper repeats the same W̄ at every
    data interpolation point), with fresh random masks V each round.
    """
    kq, km = jax.random.split(key)
    wbar = quantize.quantize_weights(kq, w, cfg.lw, cfg.r, cfg.p)
    parts = jnp.broadcast_to(wbar[None], (cfg.K, *wbar.shape))
    masks = lagrange.draw_masks(km, cfg.T, wbar.shape, cfg.p)
    return lagrange.encode(cfg.scheme, parts, masks, cfg.p)


# ---------------------------------------------------------------------------
# Split weight encode: the W-INDEPENDENT half (key split + fresh masks +
# their encoded contribution) can run while the previous round is still in
# flight; only the W-DEPENDENT half (quantize + data-row encode) must wait
# for the decoded weights.  Exactness of the field ops makes the split
# bit-identical to encode_weights (pinned in tests/test_pipeline.py).
# ---------------------------------------------------------------------------

def weight_mask_shares(cfg: CPMLConfig, key: jax.Array,
                       w_shape: tuple[int, ...]
                       ) -> tuple[jax.Array, jax.Array]:
    """W-independent half of ``encode_weights``.

    Splits the round key exactly as encode_weights does, draws the T fresh
    privacy masks (shape depends only on (d, c, r) — known before W is),
    and encodes their contribution.  Returns ``(kq, mask_shares)`` where
    ``kq`` is the stochastic-quantization key the W-dependent half consumes
    and ``mask_shares`` is (N, *w_shape, r).
    """
    kq, km = jax.random.split(key)
    wbar_shape = (*w_shape, cfg.r)
    masks = lagrange.draw_masks(km, cfg.T, wbar_shape, cfg.p)
    return kq, lagrange.encode_masks(cfg.scheme, masks, cfg.p)


def encode_weights_finish(cfg: CPMLConfig, kq: jax.Array,
                          mask_shares: jax.Array, w: jax.Array) -> jax.Array:
    """W-dependent half: quantize w, encode the data rows, add the masks.

    ``encode_weights_finish(cfg, *weight_mask_shares(cfg, key, w.shape), w)
    == encode_weights(cfg, key, w)`` bit-for-bit.
    """
    wbar = quantize.quantize_weights(kq, w, cfg.lw, cfg.r, cfg.p)
    parts = jnp.broadcast_to(wbar[None], (cfg.K, *wbar.shape))
    data = lagrange.encode_data(cfg.scheme, parts, cfg.p)
    return field.addmod(data, mask_shares, cfg.p)
