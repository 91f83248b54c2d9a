"""End-to-end CodedPrivateML protocol tests (paper Alg. 1, Thm. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field, protocol, sigmoid_poly, quantize
from repro.data import synthetic


def small_cfg(**kw):
    base = dict(N=8, K=2, T=1, r=1, backend="vmap")
    base.update(kw)
    return protocol.CPMLConfig(**base)


@pytest.fixture(scope="module")
def dataset():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=600, d=50)


def test_threshold_enforced():
    with pytest.raises(AssertionError):
        protocol.CPMLConfig(N=6, K=2, T=1, r=1)   # needs (3)(2)+1 = 7


def test_gradient_matches_cleartext(dataset):
    """One coded step == the same update computed in the clear (on the
    quantized data with the polynomial surrogate), up to quantization noise
    in the W̄ draw (eliminated by fixing the key)."""
    x, y = dataset
    cfg = small_cfg()
    key = jax.random.PRNGKey(3)
    state = protocol.setup(cfg, key, x, y)
    w0 = jnp.zeros(x.shape[1])
    eta = 0.5
    new = protocol.step(cfg, jax.random.PRNGKey(9), state, eta)
    # cleartext replica: same quantized weights, same surrogate
    kq, km = jax.random.split(jax.random.PRNGKey(9))
    kq2, _ = jax.random.split(kq)
    wbar = quantize.quantize_weights(kq2, w0, cfg.lw, cfg.r, cfg.p)
    coeffs = sigmoid_poly.fit_sigmoid(cfg.r)
    gb = sigmoid_poly.gbar_real(state.xq_real, wbar, coeffs, cfg.lx, cfg.lw)
    grad = (state.xq_real.T @ gb - state.xty) / state.m
    want = w0 - eta * grad
    got = new.w
    err = float(jnp.abs(got - want).max())
    # residual = coefficient quantization of c_i (lc bits) only
    assert err < 2e-2, err


def test_convergence_matches_uncoded(dataset):
    x, y = dataset
    cfg = small_cfg()
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=10,
                             eval_every=10)
    state = protocol.setup(cfg, jax.random.PRNGKey(7), x, y)
    eta = protocol.lipschitz_eta(state.xq_real)
    w2 = jnp.zeros(x.shape[1])
    xq, yy = state.xq_real[:600], y
    for _ in range(10):
        w2 = w2 - eta * (xq.T @ (protocol.sigmoid(xq @ w2) - yy)) / 600
    l_coded, _ = protocol.loss_and_accuracy(w, xq, yy)
    l_clear, _ = protocol.loss_and_accuracy(w2, xq, yy)
    # "comparable convergence" (paper Fig. 4): surrogate slope differs from
    # the true sigmoid derivative, so a small trajectory gap is expected.
    assert abs(float(l_coded) - float(l_clear)) < 2e-2
    assert hist[-1]["loss"] < 0.69   # improved from ln 2


@pytest.mark.parametrize("pattern", [
    np.arange(7),                      # exactly threshold, drop worker 7
    np.array([7, 6, 5, 4, 3, 2, 1]),   # reversed order, drop worker 0
    np.array([0, 2, 3, 5, 6, 7, 1]),   # shuffled
])
def test_straggler_tolerance(dataset, pattern):
    """K=2,T=1,r=1 -> threshold 7 of N=8: any 7 workers give the SAME w."""
    x, y = dataset
    cfg = small_cfg()
    state0 = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    full = protocol.step(cfg, jax.random.PRNGKey(1), state0, 0.5)
    part = protocol.step(cfg, jax.random.PRNGKey(1), state0, 0.5,
                         survivors=pattern)
    assert np.allclose(np.asarray(full.w), np.asarray(part.w), atol=1e-6)


def test_too_few_survivors(dataset):
    x, y = dataset
    cfg = small_cfg()
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    with pytest.raises(AssertionError):
        protocol.step(cfg, jax.random.PRNGKey(1), state, 0.5,
                      survivors=np.arange(6))


def test_kernel_path_equals_jnp_path(dataset):
    x, y = dataset
    c1 = small_cfg(use_kernel=False)
    c2 = small_cfg(use_kernel=True)
    s1 = protocol.setup(c1, jax.random.PRNGKey(0), x, y)
    s2 = protocol.setup(c2, jax.random.PRNGKey(0), x, y)
    w1 = protocol.step(c1, jax.random.PRNGKey(1), s1, 0.5).w
    w2 = protocol.step(c2, jax.random.PRNGKey(1), s2, 0.5).w
    assert np.allclose(np.asarray(w1), np.asarray(w2), atol=1e-7)


def test_r2_polynomial(dataset):
    """Degree-2 surrogate: threshold (5)(K+T-1)+1; still converges.

    r=2 at the paper's 24-bit prime WRAPS (headroom < 0) — documented
    overflow trade-off (§3.1); the P30 extension restores correctness."""
    x, y = dataset
    cfg24 = protocol.CPMLConfig(N=11, K=2, T=1, r=2)
    assert cfg24.headroom_bits(x_max=1.0, m=600) < 0     # would overflow
    cfg = protocol.CPMLConfig(N=11, K=2, T=1, r=2, p=field.P30)
    assert cfg.headroom_bits(x_max=1.0, m=600) > 0
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=8,
                             eval_every=8)
    assert hist[-1]["loss"] < 0.69


def test_extended_prime(dataset):
    """P30 run: more headroom, same convergence."""
    x, y = dataset
    cfg = small_cfg(p=field.P30, lc=10)
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=8,
                             eval_every=8)
    assert hist[-1]["loss"] < 0.69


@pytest.mark.parametrize("p, c, m", [(field.P, 1, 37), (field.P30, 3, 40)],
                         ids=["P-padded", "P30-c3"])
def test_compiled_dataset_encode_equals_field_matmul(p, c, m):
    """The compiled encode_dataset against the general field.matmul over
    the same quantized parts and the same masks."""
    from repro.core import lagrange
    from repro.core.protocol import encode
    cfg = small_cfg(p=p, c=c, K=3, N=10)
    x, _ = synthetic.mnist_like(jax.random.PRNGKey(m), m=m, d=7)
    key = jax.random.PRNGKey(5)
    shares, ctx = protocol.encode_dataset(cfg, key, x)
    xq = encode.pad_rows(quantize.quantize_data(x, cfg.lx, p), cfg.K)
    parts = xq.reshape(cfg.K, -1, x.shape[1])
    masks = lagrange.draw_masks(key, cfg.T, parts.shape[1:], p)
    flat = jnp.concatenate([parts, masks]).reshape(cfg.K + cfg.T, -1)
    U = jnp.asarray(cfg.scheme.encode_matrix, jnp.int32)
    want = field.matmul(U.T, flat, p).reshape(cfg.N, *parts.shape[1:])
    assert np.array_equal(np.asarray(shares), np.asarray(want))
    assert np.array_equal(np.asarray(ctx["xq"]), np.asarray(xq))
    assert type(ctx["m_padded"]) is int and ctx["m_padded"] == xq.shape[0]


def test_train_traces_the_dataset_encode_once():
    """Two jobs of the same shapes share one compiled encode: the jit's
    cache grows by one program at the first job and not at the second,
    while each job still encodes with its own key."""
    from repro.core.protocol import encode
    cfg = small_cfg(K=3, N=10)
    x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=59, d=11)
    before = encode._encode_dataset._cache_size()
    w1, _ = protocol.train(cfg, jax.random.PRNGKey(1), x, y, iters=2)
    after_first = encode._encode_dataset._cache_size()
    w2, _ = protocol.train(cfg, jax.random.PRNGKey(2), x, y, iters=2)
    assert after_first == before + 1
    assert encode._encode_dataset._cache_size() == after_first
    assert not np.array_equal(np.asarray(w1), np.asarray(w2))
