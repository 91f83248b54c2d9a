"""Plain reference: private logistic regression as CodedPrivateML trains it.

arXiv:1902.00641, Algorithm 1, with the coding taken away. Lagrange coding
is exact over the field, so the master decodes exactly the integers this
file computes in the clear, as long as nothing wraps mod p:

  * X̄ = Round(2^lx X), deterministic round-half-up (Eq. 6), padded with
    zero rows to K equal parts of m_pad / K rows;
  * each round, r unbiased stochastic quantizations W̄ of the weights at
    scale 2^lw (Eqs. 8-10);
  * ḡ = sum_i c̄_i prod_{j<=i} (X̄ W̄^j), with the least-squares degree-r fit
    of the sigmoid on [fit_lo, fit_hi] quantized at 2^(lc + (r-i)(lx+lw))
    (Eqs. 15-17), so every term has the scale lc + r(lx+lw);
  * per part k the integer X̄_kᵀ ḡ_k; the gradient is the sum over the parts
    of their real values 2^-(lc+lx+r(lx+lw)) X̄_kᵀ ḡ_k, less X̄ᵀ y 2^-lx, and
    the step w <- w - (eta / m)(gradient) from w = 0 (Eq. 4);
  * eta = 4 m_pad / lambda_max(X̄ᵀX̄) by a 50-step power iteration (the
    step size as the configuration states it; the paper's Lemma 2 omits the
    1/m that its Eq. 1 carries).

The random streams are the algorithm's own: a job key splits into a set-up
key and a loop key, round t's key is ``fold_in(loop key, t)``, and its first
split draws the stochastic-rounding uniforms. The privacy masks cancel in
the decode and have no part here.

Why the arithmetic is written as it is: the weights go through a
stochastic rounding every round, and a uniform draw that falls within a
rounding error of a weight's fraction rounds one way or the other on a
difference in the last bit. One such tie changes the weights after 50
rounds by about 1e-4 of their norm, which is more than the bfloat16 control
changes them. So the exact reference takes no rounding the algorithm does
not: the integer products are int32 (exact below 2^31), the parts are
summed as reals in the order of the decode (one reduction over the K
parts), and the power iteration runs op by op at the default precision, as
a job's set-up runs it. Nothing of the program is imported.

The control (``BF16``) computes the same rounds with bfloat16 operands in
every matrix product (one MXU pass), the step below the float32 that the
real-valued parts of the configuration state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXACT = "exact"
BF16 = "bf16"


def sigmoid_coeffs(cfg: dict) -> np.ndarray:
    """Quantized surrogate coefficients c̄_0..c̄_r as signed integers."""
    r, lx, lw, lc = cfg["r"], cfg["lx"], cfg["lw"], cfg["lc"]
    lo, hi, num = cfg["sigmoid_fit"]
    z = np.linspace(lo, hi, num)
    v = np.stack([z ** i for i in range(r + 1)], axis=1)
    coeffs, *_ = np.linalg.lstsq(v, 1.0 / (1.0 + np.exp(-z)), rcond=None)
    return np.array([int(round(float(c) * 2 ** (lc + (r - i) * (lx + lw))))
                     for i, c in enumerate(coeffs)], dtype=np.int64)


def _bf16_dot(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _int_dot(a, b, batch: bool = False):
    """Exact int32 product; with ``batch`` over a leading part axis,
    contracting the rows: (K, mk, d) x (K, mk, c) -> (K, d, c)."""
    dims = ((((1,), (1,)), ((0,), (0,))) if batch
            else (((a.ndim - 1,), (0,)), ((), ())))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.int32)


def padded_data(x: jax.Array, cfg: dict) -> jax.Array:
    """X̄ as int32, zero rows appended to m_pad = K ceil(m / K)."""
    xbar = jnp.floor(x * (2.0 ** cfg["lx"]) + 0.5).astype(jnp.int32)
    pad = (-x.shape[0]) % cfg["K"]
    return jnp.concatenate([xbar, jnp.zeros((pad, x.shape[1]), jnp.int32)])


def targets(y: jax.Array, c: int, rows: int) -> jax.Array:
    y = jnp.concatenate([y, jnp.zeros((rows - y.shape[0],), y.dtype)])
    if c == 1:
        return y.astype(jnp.float32)[:, None]
    return jax.nn.one_hot(y.astype(jnp.int32), c, dtype=jnp.float32)


def step_size(xr: jax.Array, precision: str) -> jax.Array:
    """eta = 4 m_pad / lambda_max of the dequantized, padded X̄."""
    m_pad, d = xr.shape
    if precision == BF16:
        return _bf16_step_size(xr)
    v = jnp.ones((d,), jnp.float32) / np.sqrt(d)
    for _ in range(50):
        v = xr.T @ (xr @ v)
        v = v / (jnp.linalg.norm(v) + 1e-30)
    lam = v @ (xr.T @ (xr @ v))
    return jnp.float32(float(4.0 * m_pad / lam))


@jax.jit
def _bf16_step_size(xr):
    m_pad, d = xr.shape

    def body(_, v):
        v = _bf16_dot(xr.T, _bf16_dot(xr, v[:, None]))[:, 0]
        return v / (jnp.linalg.norm(v) + 1e-30)

    v = jax.lax.fori_loop(0, 50, body,
                          jnp.ones((d,), jnp.float32) / np.sqrt(d))
    lam = v @ _bf16_dot(xr.T, _bf16_dot(xr, v[:, None]))[:, 0]
    return 4.0 * m_pad / lam


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _train(cfg_key, precision, xbar, tgt, key, rounds, eta, m, cbar):
    cfg = dict(cfg_key)
    lx, lw, r, k_parts = cfg["lx"], cfg["lw"], cfg["r"], cfg["K"]
    scale_l = cfg["lc"] + lx + r * (lx + lw)
    rows, d = xbar.shape
    c = tgt.shape[1]
    xr = xbar.astype(jnp.float32) * (2.0 ** -lx)
    xty = (_bf16_dot(xr.T, tgt) if precision == BF16 else
           jnp.matmul(xr.T, tgt, precision=jax.lax.Precision.HIGHEST))
    step = eta / m.astype(jnp.float32)
    _, kloop = jax.random.split(key)
    exact = precision != BF16

    def body(t, w):
        kq, _ = jax.random.split(jax.random.fold_in(kloop, t))
        scaled = w * (2.0 ** lw)
        low = jnp.floor(scaled)
        u = jax.random.uniform(kq, (d, c, r))
        wbar = low[..., None] + (u < (scaled - low)[..., None])   # (d, c, r)
        if exact:
            wbar = wbar.astype(jnp.int32)
            g = jnp.broadcast_to(cbar[0], (rows, c))
            prod = None
            for i in range(1, r + 1):
                z = _int_dot(xbar, wbar[:, :, i - 1])             # (rows, c)
                prod = z if prod is None else prod * z
                g = g + cbar[i] * prod
            parts = _int_dot(xbar.reshape(k_parts, rows // k_parts, d),
                             g.reshape(k_parts, rows // k_parts, c),
                             batch=True)                          # (K, d, c)
            xg = (parts.astype(jnp.float32) * (2.0 ** -scale_l)).sum(axis=0)
        else:
            xf = xbar.astype(jnp.float32)
            g = jnp.broadcast_to(cbar[0].astype(jnp.float32), (rows, c))
            prod = None
            for i in range(1, r + 1):
                z = _bf16_dot(xf, wbar[:, :, i - 1])
                prod = z if prod is None else prod * z
                g = g + cbar[i].astype(jnp.float32) * prod
            xg = _bf16_dot(xf.T, g) * (2.0 ** -scale_l)
        return w - step * (xg - xty)

    return jax.lax.fori_loop(0, rounds, body, jnp.zeros((d, c), jnp.float32))


def train(cfg: dict, x: jax.Array, y: jax.Array, key: jax.Array,
          rounds: int, precision: str = EXACT) -> jax.Array:
    """Weights (d, c) after ``rounds`` rounds of the job keyed ``key``."""
    xbar = padded_data(x, cfg)
    eta = step_size(xbar.astype(jnp.float32) * (2.0 ** -cfg["lx"]),
                    precision)
    cfg_key = tuple(sorted((k, cfg[k]) for k in ("lx", "lw", "lc", "r", "K")))
    cbar = jnp.asarray(sigmoid_coeffs(cfg), jnp.int32)
    return _train(cfg_key, precision, xbar,
                  targets(y, cfg["c"], xbar.shape[0]), key,
                  jnp.int32(rounds), eta, jnp.int32(x.shape[0]), cbar)
