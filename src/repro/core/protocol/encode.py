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

from repro.core import field, lagrange, quantize
from repro.core.protocol.config import CPMLConfig


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


def encode_dataset(cfg: CPMLConfig, key: jax.Array, x: jax.Array
                   ) -> tuple[jax.Array, dict[str, Any]]:
    """Returns shares (N, m/K, d) + master-side cleartext context.

    Quantize, pad, split, mask draw and encode run as ONE compiled program
    (``cfg`` static), traced once per (cfg, shapes): each call still draws
    its own masks from ``key`` and encodes its own ``x``.
    """
    shares, xq = _encode_dataset(cfg, key, x)
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
