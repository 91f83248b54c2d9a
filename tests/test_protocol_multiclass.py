"""Multi-class + mini-batch protocol tests (acceptance: exact over F_p).

The coded pipeline must reproduce the cleartext quantized computation
EXACTLY in the field domain: decode_parts(worker results) == the per-part
sub-gradient X̄_kᵀ ḡ(X̄_k, W̄) mod p computed directly on the quantized data.
No tolerance — these are integers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field, protocol, quantize, sigmoid_poly
from repro.data import synthetic


def mc_cfg(**kw):
    base = dict(N=8, K=2, T=1, r=1, c=3, backend="vmap")
    base.update(kw)
    return protocol.CPMLConfig(**base)


@pytest.fixture(scope="module")
def dataset():
    return synthetic.multiclass_mnist_like(jax.random.PRNGKey(42), m=300,
                                           d=24, c=3)


def _clear_field_subgradients(cfg, xq_parts_field, wbar):
    """Direct F_p computation of h_k = X̄_kᵀ ḡ(X̄_k, W̄) for every part."""
    d, c, r = wbar.shape
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(
        cfg.r, cfg.lx, cfg.lw, cfg.lc, cfg.p), jnp.int32)
    out = []
    for k in range(cfg.K):
        z = field.matmul(xq_parts_field[k], wbar.reshape(d, c * r), cfg.p)
        s = sigmoid_poly.gbar_field(
            z.reshape(z.shape[0], c, r), cbar, cfg.p)            # (mk, c)
        out.append(field.matmul(xq_parts_field[k].T, s, cfg.p))  # (d, c)
    return jnp.stack(out)


@pytest.mark.parametrize("worker_path", ["reference", "fused"], indirect=True)
@pytest.mark.parametrize("pattern", [np.arange(8),
                                     np.array([7, 5, 3, 1, 0, 2, 4])])
def test_multiclass_gradient_exact_over_field(dataset, worker_path, pattern):
    """c=3 coded step decodes the EXACT field sub-gradients of the
    cleartext quantized baseline, for any valid survivor pattern, on
    either worker path."""
    x, y = dataset
    cfg = mc_cfg()
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x_shares, ctx = protocol.encode_dataset(cfg, kx, x)
    d = x.shape[1]
    w2 = jax.random.normal(jax.random.PRNGKey(3), (d, cfg.c)) * 0.1

    w_shares = protocol.encode_weights(cfg, kw, w2)      # (N, d, c, r)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(
        cfg.r, cfg.lx, cfg.lw, cfg.lc, cfg.p), jnp.int32)
    results = protocol.all_worker_results(cfg, cbar, x_shares, w_shares)

    surv = pattern[: cfg.threshold]
    dmat = protocol.make_decode_matrix(cfg, surv)
    decoded = protocol.decode_parts(cfg, results[jnp.asarray(surv)], dmat)

    # cleartext replica: same W̄ draw (same key path as encode_weights)
    kq, _ = jax.random.split(kw)
    wbar = quantize.quantize_weights(kq, w2, cfg.lw, cfg.r, cfg.p)
    xq = protocol.pad_rows(quantize.quantize_data(x, cfg.lx, cfg.p), cfg.K)
    xq_parts = xq.reshape(cfg.K, -1, d)
    want = _clear_field_subgradients(cfg, xq_parts, wbar)

    assert np.array_equal(np.asarray(decoded), np.asarray(want))


def test_minibatch_gradient_exact_over_field(dataset):
    """Row-subset of the ONCE-encoded shares decodes the exact field
    sub-gradients of the same row-subset of the cleartext parts — the
    property that makes coded mini-batch SGD sound (DESIGN.md §6)."""
    x, y = dataset
    b = 48
    cfg = mc_cfg(batch_rows=b)
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x_shares, ctx = protocol.encode_dataset(cfg, kx, x)
    mk = ctx["m_padded"] // cfg.K
    d = x.shape[1]
    idx = jax.random.choice(jax.random.PRNGKey(9), mk, (b,), replace=False)
    w2 = jax.random.normal(jax.random.PRNGKey(4), (d, cfg.c)) * 0.1

    w_shares = protocol.encode_weights(cfg, kw, w2)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(
        cfg.r, cfg.lx, cfg.lw, cfg.lc, cfg.p), jnp.int32)
    xb = jnp.take(x_shares, idx, axis=1)                 # (N, b, d)
    results = protocol.all_worker_results(cfg, cbar, xb, w_shares)
    dmat = protocol.make_decode_matrix(cfg, np.arange(cfg.N))
    decoded = protocol.decode_parts(cfg, results[: cfg.threshold], dmat)

    kq, _ = jax.random.split(kw)
    wbar = quantize.quantize_weights(kq, w2, cfg.lw, cfg.r, cfg.p)
    xq = protocol.pad_rows(quantize.quantize_data(x, cfg.lx, cfg.p), cfg.K)
    xq_parts = jnp.take(xq.reshape(cfg.K, mk, d), idx, axis=1)
    want = _clear_field_subgradients(cfg, xq_parts, wbar)

    assert np.array_equal(np.asarray(decoded), np.asarray(want))


def test_multiclass_step_matches_cleartext_real(dataset):
    """Full real-domain step: coded (d, c) update == cleartext surrogate
    update on the quantized data, up to sigmoid-coefficient quantization."""
    x, y = dataset
    cfg = mc_cfg()
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    eta = 0.5
    new = protocol.step(cfg, jax.random.PRNGKey(9), state, eta)
    assert new.w.shape == (x.shape[1], cfg.c)

    kq, _ = jax.random.split(jax.random.split(jax.random.PRNGKey(9))[0])
    w0 = jnp.zeros((x.shape[1], cfg.c))
    wbar = quantize.quantize_weights(
        jax.random.split(jax.random.PRNGKey(9))[0], w0, cfg.lw, cfg.r, cfg.p)
    coeffs = sigmoid_poly.fit_sigmoid(cfg.r)
    onehot = jax.nn.one_hot(state.y[: state.m], cfg.c)
    gb = jnp.stack([
        sigmoid_poly.gbar_real(state.xq_real, wbar[:, cls], coeffs,
                               cfg.lx, cfg.lw, cfg.p)
        for cls in range(cfg.c)], axis=1)                # (m_padded, c)
    grad = (state.xq_real.T @ gb - state.xty) / state.m
    want = w0 - eta * grad
    err = float(jnp.abs(new.w - want).max())
    assert err < 2e-2, err


def test_multiclass_straggler_tolerance(dataset):
    """Any threshold-sized survivor set yields the SAME (d, c) update."""
    x, y = dataset
    cfg = mc_cfg()
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    full = protocol.step(cfg, jax.random.PRNGKey(1), state, 0.5)
    part = protocol.step(cfg, jax.random.PRNGKey(1), state, 0.5,
                         survivors=np.array([6, 4, 2, 0, 1, 3, 5]))
    assert np.allclose(np.asarray(full.w), np.asarray(part.w), atol=1e-6)


def test_multiclass_convergence(dataset):
    """10-class-style training beats the uniform-prediction loss and tracks
    the cleartext baseline (paper Fig. 4, generalized)."""
    x, y = dataset
    cfg = mc_cfg()
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=15,
                             eval_every=15)
    state = protocol.setup(cfg, jax.random.PRNGKey(7), x, y)
    eta = protocol.lipschitz_eta(state.xq_real)
    xq = state.xq_real[: state.m]
    onehot = jax.nn.one_hot(y, cfg.c)
    wc = jnp.zeros((x.shape[1], cfg.c))
    for _ in range(15):
        wc = wc - eta * (xq.T @ (protocol.sigmoid(xq @ wc) - onehot)) / state.m
    l_clear, _ = protocol.multiclass_loss_and_accuracy(wc, xq, y)
    assert hist[-1]["loss"] < 0.6365        # improved from -log sigmoid(0)
    assert abs(hist[-1]["loss"] - float(l_clear)) < 2e-2
