"""Lagrange coded computing: correctness, thresholds, privacy (paper §3.2/A.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field, lagrange


def _scheme(N=9, K=3, T=2):
    return lagrange.CodingScheme(N=N, K=K, T=T)


def test_encode_decode_identity(key):
    s = _scheme()
    parts = jax.random.randint(key, (3, 4, 5), 0, field.P, dtype=jnp.int32)
    masks = lagrange.draw_masks(jax.random.PRNGKey(1), 2, (4, 5))
    shares = lagrange.encode(s, parts, masks)
    assert shares.shape == (9, 4, 5)
    dec = lagrange.decode(s, shares, np.arange(9), deg_f=1)
    assert np.array_equal(np.asarray(dec), np.asarray(parts))


@pytest.mark.parametrize("survivor_seed", [0, 1, 2, 3])
def test_decode_from_any_threshold_subset(key, survivor_seed):
    """ANY deg_f*(K+T-1)+1 workers suffice — the straggler property."""
    s = _scheme(N=9, K=3, T=2)
    parts = jax.random.randint(key, (3, 6), 0, field.P, dtype=jnp.int32)
    masks = lagrange.draw_masks(jax.random.PRNGKey(1), 2, (6,))
    shares = lagrange.encode(s, parts, masks)
    need = lagrange.degree_threshold(3, 2, 1)     # = 5
    rng = np.random.default_rng(survivor_seed)
    surv = rng.choice(9, size=need, replace=False)
    dec = lagrange.decode(s, shares[jnp.asarray(surv)], surv, deg_f=1)
    assert np.array_equal(np.asarray(dec), np.asarray(parts))


def test_decode_polynomial_computation(key):
    """Workers compute f(x) = x*x elementwise (deg 2); decode recovers
    f(parts) from (2)(K+T-1)+1 results — the h(z)=f(u(z)) argument."""
    s = _scheme(N=9, K=2, T=1)
    parts = jax.random.randint(key, (2, 8), 0, field.P, dtype=jnp.int32)
    masks = lagrange.draw_masks(jax.random.PRNGKey(1), 1, (8,))
    shares = lagrange.encode(s, parts, masks)
    results = field.mulmod(shares, shares, field.P)       # per-worker f
    need = lagrange.degree_threshold(2, 1, 2)             # 2*(2)+1 = 5
    surv = np.array([8, 3, 5, 0, 6])
    dec = lagrange.decode(s, results[jnp.asarray(surv)], surv, deg_f=2)
    want = field.mulmod(parts, parts, field.P)
    assert np.array_equal(np.asarray(dec), np.asarray(want))


def test_below_threshold_fails():
    s = _scheme(N=9, K=3, T=2)
    with pytest.raises(AssertionError):
        lagrange.decode(s, jnp.zeros((4, 2), jnp.int32), np.arange(4), 1)


def test_recovery_threshold_formula():
    assert lagrange.recovery_threshold(K=13, T=1, r=1) == 3 * 13 + 1
    assert lagrange.recovery_threshold(K=7, T=7, r=1) == 3 * 13 + 1
    assert lagrange.recovery_threshold(K=2, T=1, r=2) == 5 * 2 + 1


def test_mds_bottom_block():
    """Privacy (App. A.4): every T x T submatrix of U_bottom is invertible,
    so T shares are one-time-padded by the uniform masks."""
    s = _scheme(N=8, K=3, T=2)
    U = s.encode_matrix                      # (K+T, N)
    bottom = U[3:, :]                        # (T, N)
    from itertools import combinations
    p = field.P
    for cols in combinations(range(8), 2):
        sub = bottom[:, cols].astype(object)
        det = (sub[0, 0] * sub[1, 1] - sub[0, 1] * sub[1, 0]) % p
        assert det != 0, f"singular T x T block at {cols}"


def test_shares_uniform_given_masks(key):
    """With T=1, a single worker's share of ZERO data is exactly
    (mask * u_i) — uniform.  Check the map mask -> share is a bijection
    (distribution-preserving), i.e. the coefficient is nonzero."""
    s = _scheme(N=5, K=2, T=1)
    U = s.encode_matrix
    assert (U[2, :] != 0).all()   # mask row coefficient never vanishes


def test_t_collusion_independence(key):
    """Empirical privacy: encode the SAME dataset with fresh masks; any
    single worker's share distribution should cover the field uniformly.
    (chi^2-lite: bucket means close to uniform.)"""
    s = _scheme(N=5, K=2, T=1)
    parts = jnp.ones((2, 16), jnp.int32)     # constant data
    samples = []
    for i in range(200):
        masks = lagrange.draw_masks(jax.random.PRNGKey(i), 1, (16,))
        shares = lagrange.encode(s, parts, masks)
        samples.append(np.asarray(shares[0]).ravel())
    vals = np.concatenate(samples).astype(np.float64) / field.P
    # uniform on [0,1): mean ~ 0.5, var ~ 1/12
    assert abs(vals.mean() - 0.5) < 0.02
    assert abs(vals.var() - 1 / 12) < 0.005


def _oracle_encode(U: np.ndarray, stacked: np.ndarray, p: int) -> np.ndarray:
    """shares[n, e] = sum_k U[k, n] * stacked[k, e] mod p in python ints."""
    U = U.astype(object)
    flat = stacked.reshape(stacked.shape[0], -1).astype(object)
    return (U.T.dot(flat) % p).astype(np.int64)


@pytest.mark.parametrize("p", [field.P, field.P30], ids=["P", "P30"])
@pytest.mark.parametrize("T", [0, 1, 3])
@pytest.mark.parametrize("part_shape", [(1,), (3, 5), (10, 13)],
                         ids=["width1", "odd", "width130"])
def test_encode_combination_matches_int_oracle(p, T, part_shape):
    """The (K+T)-term limb combination against python ints, at flat widths
    1, odd and not a multiple of 128; its split halves add up to it."""
    s = lagrange.CodingScheme(N=13, K=4, T=T, p=p)
    rng = np.random.default_rng(T)
    parts = rng.integers(0, p, (4, *part_shape))
    parts[0] = p - 1                                   # every limb at 255
    masks = rng.integers(0, p, (T, *part_shape))
    shares = lagrange.encode(s, jnp.asarray(parts, jnp.int32),
                             jnp.asarray(masks, jnp.int32), p)
    stacked = np.concatenate([parts, masks])
    want = _oracle_encode(s.encode_matrix, stacked, p)
    assert shares.shape == (13, *part_shape)
    assert np.array_equal(np.asarray(shares).reshape(13, -1), want)
    split = field.addmod(
        lagrange.encode_data(s, jnp.asarray(parts, jnp.int32), p),
        lagrange.encode_masks(s, jnp.asarray(masks, jnp.int32), p), p)
    assert np.array_equal(np.asarray(split), np.asarray(shares))


@pytest.mark.parametrize("p, rows", [(field.P, 80), (field.P30, 65)],
                         ids=["P", "P30"])
def test_combine_deep_contraction_falls_back_exactly(monkeypatch, p, rows):
    """From nl·rows·255² >= min(p, 2^24) on, the f32 sums could round or
    pass p, so the combination takes field.matmul; one row less stays on
    the limb dot.  Both are exact."""
    calls = []
    matmul = field.matmul
    monkeypatch.setattr(field, "matmul",
                        lambda *a, **k: calls.append(1) or matmul(*a, **k))
    rng = np.random.default_rng(rows)
    for r in (rows - 1, rows):
        U = rng.integers(0, p, (r, 6))
        U[0] = p - 1
        flat = np.full((r, 9), p - 1)
        got = lagrange.combine(U, jnp.asarray(flat, jnp.int32), p)
        assert np.array_equal(np.asarray(got), _oracle_encode(U, flat, p))
        assert len(calls) == (r == rows)
