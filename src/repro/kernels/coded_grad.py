"""Pallas TPU kernel: the fused CodedPrivateML worker step (paper Eq. 20).

f(X̃, W̃) = X̃ᵀ ḡ(X̃, W̃) with ḡ = sum_i c̄_i prod_{j<=i}(X̃ w̃ʲ) mod p.

X̃ is by far the largest operand (m/K x d against d x c·r), so the kernel
streams each X̃ row block through VMEM once and does both products from it:

    per row block b:  Zᵀ_b = W̃ᵀ X̃_bᵀ        (signed 8-bit digits, MXU)
                      s_b  = ḡ(Z_b)           (VPU mod arithmetic)
                      out += s_bᵀ X̃_b         (the same X̃_b digits, MXU)

The kernel reads each share as X̃ᵀ (d, mk), a block of rows along lanes:
that is how a TPU lays out these shares in HBM (rows minor; a (40, 4616,
784) or (10, 30770, 2000) int32 array pads least so), so the swap of axes
costs nothing there.  Where the layout differs, XLA copies the shares once
before the call.

Exact field products on the int8 MXU (DESIGN.md §3, "The worker's
signed-digit contraction"): the share's elements are split into nl =
``field.n_limbs(p)`` int8 planes byte - 128 (``offset_digits``, whose
offset returns as two small correction terms), W̃ and s into balanced
base-256 digits of their centred representatives (``digits``), all in
[-128, 127].  A digit product is at most 2^14 in magnitude, so an int32
dot of depth k sums to under 2^14·k, and the nl dots of one digit weight
2^{8g} under nl·2^14·k < 2^31 while k < 2^17/nl.  The remainder is taken
once per digit weight, then Horner doublings recombine them: once per row
block for Zᵀ (depth d), and once per row block into the (c, d)
accumulator for the second product (depth: the block's rows), never per
chunk of the contraction.

One ``pallas_call`` takes every worker's share, stacked (n, mk, d), over a
grid of (worker, row block).  The row block comes from d and a VMEM budget;
rows past the end of the share (the last block's overhang) get s = 0, so
they add nothing.  W̃ (d x c·r digits) and the (c, d) accumulator stay in
VMEM: fine for logistic regression at any d the int32 bound allows
(ROADMAP B5 is the wide case).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import field

# int32 bytes of one X̃ row block in VMEM; double-buffered, with its digits
# and the digit split's temporaries, a block takes about five times this
BLOCK_BYTES = 1 << 22
VMEM_LIMIT_BYTES = 64 << 20
# heads padded to whole (8, 128) int32 tiles, so each digit's rows of the
# stacked products are aligned slices
HEAD_ROWS = 8
DIGIT_MAX = 1 << 14          # |digit product| <= 128·128


def digits(x: jax.Array, p: int) -> list[jax.Array]:
    """nl balanced base-256 digits (low first, each in [-128, 127]) of the
    centred representative of x in [0, p): sum_i d_i 2^{8i} ≡ x (mod p)."""
    nl = field.n_limbs(p)
    v = jnp.where(x > (p - 1) // 2, x - p, x)
    out = []
    for _ in range(nl - 1):
        d = (v << (32 - field.LIMB_BITS)) >> (32 - field.LIMB_BITS)
        out.append(d)
        v = (v - d) >> field.LIMB_BITS
    return out + [v]


def offset_digits(x: jax.Array, nl: int) -> list[jax.Array]:
    """nl int8 planes u_i = byte_i - 128 of x in [0, 2^{8nl}):
    x = sum_i u_i 2^{8i} + offset(nl).  Flipping bit 7 of every byte makes
    each byte, read as int8, its value less 128: one xor, then a shift and
    a truncation a plane."""
    y = x ^ jnp.int32(_signed(offset(nl)))
    return [(y >> (field.LIMB_BITS * i)).astype(jnp.int8) for i in range(nl)]


def offset(nl: int) -> int:
    """128 in every byte of nl: what ``offset_digits`` takes away."""
    return sum(128 << (field.LIMB_BITS * i) for i in range(nl))


def _signed(v: int) -> int:
    return v - (1 << 32) if v >= 1 << 31 else v


def _recombine(groups: list[jax.Array], p: int) -> jax.Array:
    """sum_g groups[g]·2^{8g} mod p, groups in int32 of either sign."""
    out = field.fmod(groups[-1], p)
    for g in reversed(groups[:-1]):
        out = field.addmod(field.double_mod(out, field.LIMB_BITS, p),
                           field.fmod(g, p), p)
    return out


def _digit_products(a: list[jax.Array], b: jax.Array, rows: int,
                    dims) -> list[jax.Array]:
    """The 2nl-1 digit-weight sums of a ⊗ b: ``a`` the nl digit planes of
    one operand, ``b`` the other's nl digits stacked in blocks of ``rows``
    (one int8 dot per plane of ``a``)."""
    nl = len(a)
    groups = [None] * (2 * nl - 1)
    for i, ai in enumerate(a):
        t = jax.lax.dot_general(b, ai, (dims, ((), ())),
                                preferred_element_type=jnp.int32)
        for j in range(nl):
            part = t[j * rows:(j + 1) * rows]
            groups[i + j] = part if groups[i + j] is None \
                else groups[i + j] + part
    return groups


def _kernel(c_ref, x_ref, w_ref, zc_ref, o_ref, *, p: int, r: int, cp: int,
            mk: int, bm: int):
    """Grid step (worker n, row block b); accumulates into o_ref (cp, d).

    w_ref holds W̃ᵀ's digits, row j·r·cp + i·cp + cls: digit j of
    realization i of head cls, so each realization's heads are one aligned
    plane of Zᵀ and one dot per X̃ digit feeds all c·r columns.  X̃'s
    digits are offset ones (``offset_digits``, the fewest VPU operations an
    element); the offset k0 adds k0·(1ᵀW̃) to Zᵀ, given in zc_ref, and
    k0·(sᵀ1) to the block's (c, d) product."""
    b = pl.program_id(1)
    nl = field.n_limbs(p)
    k0 = offset(nl) % p
    xd = offset_digits(x_ref[0], nl)                         # nl x (d, bm)
    rc = r * cp
    # Zᵀ = W̃ᵀ X̃_bᵀ mod p: (r·cp, bm)
    z = field.addmod(_recombine(_digit_products(xd, w_ref[0], rc,
                                                ((1,), (0,))), p),
                     zc_ref[0], p)
    s = jnp.full((cp, z.shape[1]), c_ref[0], jnp.int32)
    prod = None
    for i in range(r):
        zi = z[i * cp:(i + 1) * cp]
        prod = zi if prod is None else field.mulmod(prod, zi, p)
        s = field.addmod(s, field.mulmod(
            jnp.full(prod.shape, c_ref[i + 1], jnp.int32), prod, p), p)
    if mk % bm:
        row = b * bm + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row < mk, s, 0)
    sds = digits(s, p)
    sd = jnp.concatenate(sds, axis=0).astype(jnp.int8)
    # out += s_bᵀ X̃_b mod p: (cp, d)
    contrib = _recombine(_digit_products(xd, sd, cp, ((1,), (1,))), p)
    ssum = _recombine([jnp.sum(t, axis=1, keepdims=True) for t in sds], p)
    contrib = field.addmod(contrib, field.mulmod(
        ssum, jnp.full(ssum.shape, k0, jnp.int32), p), p)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] = field.addmod(o_ref[0], contrib, p)


def row_block(mk: int, d: int) -> int:
    """Rows of X̃ a grid step reads: the whole share if its int32 block fits
    BLOCK_BYTES, else equal blocks of a multiple of 128 rows (lanes)
    under it."""
    if mk * d * 4 <= BLOCK_BYTES:
        return mk
    rows = max(128, BLOCK_BYTES // (4 * d) // 128 * 128)
    n = -(-mk // rows)
    return -(-mk // (128 * n)) * 128


def _w_operands(w: jax.Array, p: int, cp: int
                ) -> tuple[jax.Array, jax.Array]:
    """(n, d, c, r) weight shares -> W̃ᵀ's balanced digits (n, nl·r·cp,
    d) int8, and k0·(1ᵀW̃) mod p (n, r·cp, 1) for X̃'s offset digits."""
    n, d, c, r = w.shape
    wt = jnp.pad(jnp.transpose(w, (0, 3, 2, 1)),          # (n, r, c, d)
                 ((0, 0), (0, 0), (0, cp - c), (0, 0))).reshape(n, -1, d)
    wds = digits(wt, p)
    colsum = _recombine([jnp.sum(t, axis=-1, keepdims=True) for t in wds], p)
    k0 = jnp.full(colsum.shape, offset(len(wds)) % p, jnp.int32)
    wd = jnp.stack(wds, axis=1).reshape(n, -1, d).astype(jnp.int8)
    return wd, field.mulmod(colsum, k0, p)


def coded_grad_workers(x: jax.Array, w: jax.Array, cbar: jax.Array,
                       p: int = field.P,
                       interpret: bool | None = None) -> jax.Array:
    """Every worker's step at once: x (n, mk, d), w (n, d, c, r),
    cbar (r+1,) -> (n, d, c) mod p."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, mk, d = x.shape
    _, _, c, r = w.shape
    nl = field.n_limbs(p)
    bm = row_block(mk, d)
    if nl * DIGIT_MAX * max(d, bm) >= 1 << 31:
        raise ValueError(f"d={d}: a digit-weight sum can overflow int32")
    cp = -(-c // HEAD_ROWS) * HEAD_ROWS
    kernel = functools.partial(_kernel, p=p, r=r, cp=cp, mk=mk, bm=bm)
    out = pl.pallas_call(
        kernel,
        grid=(n, -(-mk // bm)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, bm), lambda i, b: (i, 0, b)),
            pl.BlockSpec((1, nl * r * cp, d), lambda i, b: (i, 0, 0)),
            pl.BlockSpec((1, r * cp, 1), lambda i, b: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, cp, d), lambda i, b: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, cp, d), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="cpml_coded_grad",
    )(cbar.astype(jnp.int32), jnp.swapaxes(x, 1, 2), *_w_operands(w, p, cp))
    return jnp.transpose(out[:, :c], (0, 2, 1))


def coded_grad_mc(x: jax.Array, w: jax.Array, cbar: jax.Array,
                  p: int = field.P, interpret: bool | None = None
                  ) -> jax.Array:
    """One worker, c one-vs-all heads: x (mk, d), w (d, c, r), cbar (r+1,)
    -> (d, c) mod p; the heads share the single pass over X̃."""
    assert x.ndim == 2 and w.ndim == 3 and x.shape[1] == w.shape[0]
    return coded_grad_workers(x[None], w[None], cbar, p, interpret)[0]
