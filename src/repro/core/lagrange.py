"""Lagrange coded computing (paper §3.2, §3.4; Yu et al. 2019).

Encoding: split X̄ into K submatrices, append T uniform random masks, fit the
degree-(K+T-1) interpolant u with u(beta_i) = X̄_i (i<=K) / Z_i (i>K), and
evaluate at N points alpha -> shares X̃_i = u(alpha_i).  Equivalently a
mod-p matmul against the (K+T, N) encoding matrix U (Eq. 12).

Decoding: worker i returns h(alpha_i) where h = f(u(z), v(z)) has degree
<= deg(f)·(K+T-1).  Any R = deg(f)·(K+T-1)+1 surviving evaluations determine
h; we read off h(beta_k) via a second Lagrange-coefficient matrix (no
Vandermonde inversion needed on the hot path).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import field


def recovery_threshold(K: int, T: int, r: int) -> int:
    """Minimum surviving workers: (2r+1)(K+T-1)+1 (Theorem 1)."""
    return (2 * r + 1) * (K + T - 1) + 1


def degree_threshold(K: int, T: int, deg_f: int) -> int:
    """Threshold for an arbitrary polynomial worker function of degree deg_f."""
    return deg_f * (K + T - 1) + 1


@dataclasses.dataclass(frozen=True)
class CodingScheme:
    """All static data of one Lagrange code: evaluation points + matrices."""
    N: int          # number of workers / shares
    K: int          # parallelization (dataset split)
    T: int          # privacy threshold
    p: int = field.P

    def __post_init__(self):
        assert self.K >= 1 and self.T >= 0 and self.N >= self.K + self.T, (
            f"need N >= K+T, got N={self.N} K={self.K} T={self.T}")

    @functools.cached_property
    def betas(self) -> np.ndarray:
        # K+T distinct interpolation points: 1..K+T (disjoint from alphas).
        return np.arange(1, self.K + self.T + 1, dtype=np.int64)

    @functools.cached_property
    def alphas(self) -> np.ndarray:
        # N distinct evaluation points, disjoint from betas.
        start = self.K + self.T + 1
        return np.arange(start, start + self.N, dtype=np.int64)

    @functools.cached_property
    def encode_matrix(self) -> np.ndarray:
        """U in F_p^{(K+T) x N} of Eq. (12)."""
        return field.host_lagrange_coeffs(self.alphas, self.betas, self.p)

    def decode_matrix(self, survivors: np.ndarray) -> np.ndarray:
        """D in F_p^{len(survivors) x K}: h(beta_k) = sum_i D[i,k] h(alpha_i).

        survivors: indices (into [N]) of workers whose results arrived.
        """
        pts = self.alphas[np.asarray(survivors)]
        return field.host_lagrange_coeffs(self.betas[: self.K], pts, self.p)

    def coeff_matrix(self, survivors: np.ndarray) -> np.ndarray:
        """V^{-1}: recovers the coefficients of h from survivor evaluations."""
        pts = self.alphas[np.asarray(survivors)]
        return field.host_vandermonde_inv(pts, self.p)


def _limb_weights(U: np.ndarray, p: int) -> np.ndarray:
    """Host constant of ``combine``: (nl, N, nl·rows) 8-bit limbs.

    Entry [j, n, i·rows + k] is limb j of 2^{8i}·U[k, n] mod p.  Against
    the data's limb i in row block i, this folds each data limb's weight
    2^{8i} into U on the host, so the device recombines only the nl
    weights 2^{8j} of U's own limbs.
    """
    nl = field.n_limbs(p)
    U = np.asarray(U, np.int64) % p
    V = np.concatenate([U * pow(2, field.LIMB_BITS * i, p) % p
                        for i in range(nl)])                 # (nl·rows, N)
    L = np.stack([(V >> (field.LIMB_BITS * j)) & field.LIMB_MASK
                  for j in range(nl)])                       # (nl, nl·rows, N)
    return L.transpose(0, 2, 1)


def combine(U: np.ndarray, flat: jax.Array, p: int,
            block: tuple[jax.Array, int] | None = None) -> jax.Array:
    """(Uᵀ @ flat) mod p for a host-constant (rows, N) U: (N, E) int32.

    ``block = (i, n)`` gives only the n output rows [i·n, (i+1)·n), the
    shares of one chip's workers (``i`` may be traced): the same columns
    of U, sliced from the constant, so each share is computed exactly as
    in the whole combination.

    The encode's contraction is only rows = K+T (or K, or T) deep, so it
    is not a general ``field.matmul`` (DESIGN.md §3, "The encode's narrow
    contraction").  ``flat`` (rows, E) is split into its nl 8-bit limbs;
    for each limb j of ``_limb_weights(U)`` one bf16 dot contracts all nl
    data limbs and all rows at once.  Each product is < 2^16 and each of
    the nl accumulators sums nl·rows of them, so while
    nl·rows·255² < min(p, 2^24) the f32 sums are exact and already below
    p, and no remainder is taken.  Horner over the accumulators in steps
    of 2^8 recombines them: 8·(nl-1) modular doublings an element.  A
    deeper contraction takes ``field.matmul``.
    """
    rows, N = U.shape
    nl = field.n_limbs(p)

    def columns(W, axis):
        if block is None:
            return W
        i, n = block
        return jax.lax.dynamic_slice_in_dim(W, i * n, n, axis)

    if nl * rows * field.LIMB_MASK ** 2 >= min(p, 1 << 24):
        Ut = jnp.asarray(np.asarray(U).T, jnp.int32)
        return field.matmul(columns(Ut, 0), flat, p)
    L = jnp.asarray(_limb_weights(U, p), jnp.bfloat16)       # (nl, N, nl·rows)
    L = columns(L.reshape(nl, N, nl, rows), 1)
    shifts = jnp.arange(nl, dtype=jnp.int32)[:, None, None] * field.LIMB_BITS
    X = ((flat[None] >> shifts) & field.LIMB_MASK).astype(jnp.bfloat16)

    def acc(j):
        return jnp.einsum("nik,ike->ne", L[j], X,
                          preferred_element_type=jnp.float32).astype(jnp.int32)
    out = acc(nl - 1)
    for j in range(nl - 2, -1, -1):
        out = field.addmod(field.double_mod(out, field.LIMB_BITS, p),
                           acc(j), p)
    return out


def encode(scheme: CodingScheme, x_parts: jax.Array, masks: jax.Array,
           p: int | None = None,
           block: tuple[jax.Array, int] | None = None) -> jax.Array:
    """Encode stacked parts+masks into N shares (Eq. 12).

    x_parts: (K, *part_shape) int32 field elements.
    masks:   (T, *part_shape) uniform field elements (the Z_i / V_i).
    Returns shares: (N, *part_shape), each element the (K+T)-term
    combination ``combine`` computes, exact mod p; with ``block = (i, n)``
    only shares [i·n, (i+1)·n), bit-identical to those rows of the whole.
    """
    p = p or scheme.p
    stacked = jnp.concatenate([x_parts, masks], axis=0) if scheme.T else x_parts
    return _encode_rows(scheme, stacked, slice(0, scheme.K + scheme.T), p,
                        block)


def _encode_rows(scheme: CodingScheme, stacked: jax.Array, rows: slice,
                 p: int, block: tuple[jax.Array, int] | None = None
                 ) -> jax.Array:
    """Shares contributed by a contiguous row-slice of the encode matrix U."""
    part_shape = stacked.shape[1:]
    flat = stacked.reshape(stacked.shape[0], -1)
    shares = combine(scheme.encode_matrix[rows], flat, p, block)
    return shares.reshape(-1, *part_shape)      # (N or n, *part_shape)


def encode_data(scheme: CodingScheme, x_parts: jax.Array,
                p: int | None = None) -> jax.Array:
    """The data-row contribution U[:K]ᵀ X̄ of a split encode.

    ``addmod(encode_data(parts), encode_masks(masks)) == encode(parts,
    masks)`` bit-for-bit: combine/addmod are exact mod p, so splitting the
    (K+T)-row combination into its K-row and T-row halves changes nothing.
    This is the W-DEPENDENT half of a round's weight encode — the only part
    that must wait for the previous round's decoded weights.
    """
    p = p or scheme.p
    return _encode_rows(scheme, x_parts, slice(0, scheme.K), p)


def encode_masks(scheme: CodingScheme, masks: jax.Array,
                 p: int | None = None) -> jax.Array:
    """The mask-row contribution U[K:]ᵀ Z of a split encode.

    Depends only on the round's random masks — never on the data or the
    weights — so a pipelined master precomputes it for round k+1 while
    round k is still in flight (cluster/pipeline.py).  T == 0 contributes
    nothing (zeros), mirroring encode()'s no-mask path.
    """
    p = p or scheme.p
    if scheme.T == 0:
        return jnp.zeros((scheme.N, *masks.shape[1:]), jnp.int32)
    return _encode_rows(scheme, masks,
                        slice(scheme.K, scheme.K + scheme.T), p)


def draw_masks(key: jax.Array, T: int, part_shape: tuple[int, ...],
               p: int = field.P) -> jax.Array:
    """T i.i.d. uniform matrices over F_p (the privacy masks)."""
    if T == 0:
        return jnp.zeros((0, *part_shape), jnp.int32)
    return jax.random.randint(key, (T, *part_shape), 0, p, dtype=jnp.int32)


def decode(scheme: CodingScheme, results: jax.Array, survivors: np.ndarray,
           deg_f: int, p: int | None = None) -> jax.Array:
    """Recover {h(beta_k)}_{k in [K]} from survivor evaluations (§3.4).

    results:   (S, *res_shape) field elements, S = len(survivors) evaluations
               h(alpha_i) in survivor order.
    survivors: static numpy index array; len >= deg_f*(K+T-1)+1.
    Returns (K, *res_shape): the K decoded sub-results.
    """
    p = p or scheme.p
    need = degree_threshold(scheme.K, scheme.T, deg_f)
    assert len(survivors) >= need, (
        f"need {need} survivors for deg(f)={deg_f}, got {len(survivors)}")
    survivors = np.asarray(survivors)[:need]
    res_shape = results.shape[1:]
    flat = results[: need].reshape(need, -1)
    D = jnp.asarray(scheme.decode_matrix(survivors), jnp.int32)  # (S, K)
    out = field.matmul(D.T, flat, p)  # (K, prod(res_shape))
    return out.reshape(scheme.K, *res_shape)


def decode_sum(scheme: CodingScheme, results: jax.Array,
               survivors: np.ndarray, deg_f: int,
               p: int | None = None) -> jax.Array:
    """sum_k h(beta_k) — the paper's Eq. (23) — in one matmul."""
    p = p or scheme.p
    decoded = decode(scheme, results, survivors, deg_f, p)
    out = decoded[0]
    for k in range(1, scheme.K):
        out = field.addmod(out, decoded[k], p)
    return out
