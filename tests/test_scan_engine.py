"""Scan-engine acceptance: the fully-jitted lax.scan train() must be
BIT-IDENTICAL to the per-step reference loop over the same schedule, for
>= 10 iterations, across backends/kernel/batching.  (The shard backend is
covered in test_system.py::test_shard_map_backend_multidevice via a forced
8-device subprocess.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol, quantize, sigmoid_poly
from repro.data import synthetic


@pytest.fixture(scope="module")
def binary_data():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=300, d=24)


@pytest.fixture(scope="module")
def mc_data():
    return synthetic.multiclass_mnist_like(jax.random.PRNGKey(42), m=300,
                                           d=24, c=3)


def rotating_survivors(n):
    return lambda t: np.roll(np.arange(n), t)


@pytest.mark.parametrize("worker_path", ["reference", "fused"], indirect=True)
def test_scan_bit_identical_binary(binary_data, worker_path):
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    kw = dict(iters=12, survivor_fn=rotating_survivors(cfg.N), eval_every=6)
    w1, h1 = protocol.train(cfg, jax.random.PRNGKey(7), x, y, **kw)
    w2, h2 = protocol.train_reference(cfg, jax.random.PRNGKey(7), x, y, **kw)
    assert w1.shape == (x.shape[1],)
    assert (np.asarray(w1) == np.asarray(w2)).all()
    assert len(h1) == len(h2) == 2
    for a, b in zip(h1, h2):
        assert a["iter"] == b["iter"]
        assert np.isclose(a["loss"], b["loss"], atol=1e-6)
        assert np.isclose(a["acc"], b["acc"], atol=1e-6)


@pytest.mark.parametrize("worker_path", ["reference", "fused"], indirect=True)
def test_scan_bit_identical_multiclass(mc_data, worker_path):
    x, y = mc_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3)
    w1, _ = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=10,
                           survivor_fn=rotating_survivors(cfg.N))
    w2, _ = protocol.train_reference(cfg, jax.random.PRNGKey(7), x, y,
                                     iters=10,
                                     survivor_fn=rotating_survivors(cfg.N))
    assert w1.shape == (x.shape[1], 3)
    assert (np.asarray(w1) == np.asarray(w2)).all()


def test_scan_bit_identical_minibatch(mc_data):
    x, y = mc_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, batch_rows=32)
    w1, _ = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=12)
    w2, _ = protocol.train_reference(cfg, jax.random.PRNGKey(7), x, y,
                                     iters=12)
    assert (np.asarray(w1) == np.asarray(w2)).all()


def test_minibatch_trains(mc_data):
    """Mini-batch SGD actually reduces the loss over the full data."""
    x, y = mc_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, batch_rows=32)
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=20,
                             eval_every=20)
    assert hist[-1]["loss"] < 0.6365       # improved from -log sigmoid(0)


def test_schedule_shapes(mc_data):
    x, y = mc_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, batch_rows=16)
    sched = protocol.make_schedule(cfg, jax.random.PRNGKey(0), 5, mk=150,
                                   survivor_fn=rotating_survivors(cfg.N))
    R = cfg.threshold
    assert sched.decode_mats.shape == (5, R, cfg.K)
    assert sched.orders.shape == (5, R)
    assert sched.batch_idx.shape == (5, 16)
    # without replacement within a round
    for t in range(5):
        assert len(set(np.asarray(sched.batch_idx[t]))) == 16


def test_minibatch_padded_row_normalization():
    """m not divisible by K: a batch containing the padded tail row must
    normalize by the REAL sample count (K*b - #padded), matching the
    cleartext mini-batch update exactly."""
    x, y = synthetic.mnist_like(jax.random.PRNGKey(0), m=299, d=16)
    b = 8
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, batch_rows=b)
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    assert state.mk == 150                      # padded 299 -> 300
    idx = jnp.asarray([149, 0, 5, 10, 20, 30, 40, 50], jnp.int32)  # 149: pad
    eta = 0.5
    new = protocol.step(cfg, jax.random.PRNGKey(9), state, eta, batch_idx=idx)

    # cleartext replica over the 2*b - 1 REAL selected samples
    kq, _ = jax.random.split(jax.random.split(jax.random.PRNGKey(9))[0])
    wbar = quantize.quantize_weights(
        jax.random.split(jax.random.PRNGKey(9))[0],
        jnp.zeros((x.shape[1], 1)), cfg.lw, cfg.r, cfg.p)
    coeffs = sigmoid_poly.fit_sigmoid(cfg.r)
    rows = jnp.concatenate([state.xq_parts[k][idx] for k in range(2)])
    ys = jnp.concatenate([state.y_parts[k][idx, 0] for k in range(2)])
    gb = sigmoid_poly.gbar_real(rows, wbar[:, 0], coeffs, cfg.lx, cfg.lw,
                                cfg.p)
    n_real = 2 * b - 1                          # row 149 of part 1 is zero
    grad = (rows.T @ gb - rows.T @ ys) / n_real
    err = float(jnp.abs(new.w - (-eta * grad)).max())
    assert err < 2e-2, err


def test_minibatch_padding_spanning_parts():
    """Degenerate m << K^2: padding spills beyond the last part (m=5, K=4
    pads 3 rows over parts 2 and 3) — the real-row count must still be
    exact per batch index."""
    x = jnp.eye(5, 4) * 0.5
    y = jnp.array([0., 1., 0., 1., 0.])
    cfg = protocol.CPMLConfig(N=13, K=4, T=0, r=1, batch_rows=1)
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    assert state.mk == 2
    idx = jnp.asarray([1], jnp.int32)   # global rows 1,3,5,7 -> 5,7 padded
    eta = 1.0
    new = protocol.step(cfg, jax.random.PRNGKey(9), state, eta, batch_idx=idx)

    wbar = quantize.quantize_weights(
        jax.random.split(jax.random.PRNGKey(9))[0],
        jnp.zeros((4, 1)), cfg.lw, cfg.r, cfg.p)
    coeffs = sigmoid_poly.fit_sigmoid(cfg.r)
    rows = state.xq_real[jnp.asarray([1, 3])]            # the 2 REAL samples
    ys = state.y[jnp.asarray([1, 3])]
    gb = sigmoid_poly.gbar_real(rows, wbar[:, 0], coeffs, cfg.lx, cfg.lw,
                                cfg.p)
    grad = (rows.T @ gb - rows.T @ ys) / 2.0             # /2, not /4 or /3
    err = float(jnp.abs(new.w - (-eta * grad)).max())
    assert err < 2e-2, err


def test_step_requires_batch_idx_consistency(mc_data):
    x, y = mc_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, batch_rows=16)
    state = protocol.setup(cfg, jax.random.PRNGKey(0), x, y)
    with pytest.raises(AssertionError):
        protocol.step(cfg, jax.random.PRNGKey(1), state, 0.5)  # no batch_idx
