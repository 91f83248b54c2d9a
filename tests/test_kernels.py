"""Pallas kernel sweeps vs the pure-jnp ref.py oracle (interpret=True).

Randomized (hypothesis) coverage lives in test_kernels_properties.py behind
``pytest.importorskip`` — hypothesis is an optional dev dependency
(DESIGN.md §8); this module is fully deterministic.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field, protocol, sigmoid_poly
from repro.kernels import coded_grad, modmatmul, ref
from conftest import exact_modmatmul

PRIMES = [field.P, field.P30]


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("shape", [
    (8, 16, 8), (128, 256, 128), (100, 300, 50), (1, 1, 1), (257, 129, 65),
    (64, 1000, 32),
])
def test_modmatmul_shapes(p, shape, rng):
    M, K, N = shape
    a = jnp.asarray(rng.integers(0, p, (M, K)), jnp.int32)
    b = jnp.asarray(rng.integers(0, p, (K, N)), jnp.int32)
    got = np.asarray(modmatmul.modmatmul(a, b, p)).astype(object)
    want = exact_modmatmul(a, b, p)
    assert (got == want).all(), f"mismatch at {shape} p={p}"


@pytest.mark.parametrize("p", PRIMES)
def test_modmatmul_extreme_values(p):
    """All entries p-1 — worst case for limb overflow."""
    a = jnp.full((32, 512), p - 1, jnp.int32)
    b = jnp.full((512, 16), p - 1, jnp.int32)
    got = np.asarray(modmatmul.modmatmul(a, b, p)).astype(object)
    assert (got == exact_modmatmul(a, b, p)).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_modmatmul_odd_shapes_deterministic(seed):
    """Fixed-seed stand-in for the hypothesis shape sweep."""
    rng = np.random.default_rng(seed)
    m, k, n = rng.integers(1, 80), rng.integers(1, 120), rng.integers(1, 60)
    a = jnp.asarray(rng.integers(0, field.P, (m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(0, field.P, (k, n)), jnp.int32)
    got = np.asarray(modmatmul.modmatmul(a, b)).astype(object)
    assert (got == exact_modmatmul(a, b, field.P)).all()


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("mk,d,r", [(64, 32, 1), (300, 64, 2), (257, 96, 3),
                                    (16, 8, 1), (130, 200, 1)])
def test_coded_grad_fused(p, mk, d, r, rng):
    """One binary head: the kernel == the unfused oracle on (d, 1, r)."""
    x = jnp.asarray(rng.integers(0, p, (mk, d)), jnp.int32)
    w = jnp.asarray(rng.integers(0, p, (d, 1, r)), jnp.int32)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(r, 2, 4, 6, p), jnp.int32)
    got = coded_grad.coded_grad_mc(x, w, cbar, p)
    want = ref.coded_grad_mc_ref(x, w, cbar, p)
    assert got.shape == (d, 1)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("mk,d,c,r", [(64, 32, 3, 1), (100, 48, 10, 2),
                                      (17, 8, 2, 3), (131, 200, 10, 1)])
def test_coded_grad_multiclass_fused(p, mk, d, c, r, rng):
    """Multi-head kernel == unfused oracle, and head cls of the (d, c)
    result == the kernel run on that head's weight column alone."""
    x = jnp.asarray(rng.integers(0, p, (mk, d)), jnp.int32)
    w = jnp.asarray(rng.integers(0, p, (d, c, r)), jnp.int32)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(r, 2, 4, 6, p), jnp.int32)
    got = coded_grad.coded_grad_mc(x, w, cbar, p)
    want = ref.coded_grad_mc_ref(x, w, cbar, p)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    for cls in (0, c - 1):
        head = coded_grad.coded_grad_mc(x, w[:, cls:cls + 1, :], cbar, p)
        assert np.array_equal(np.asarray(got[:, cls]), np.asarray(head[:, 0]))


def test_ref_oracle_against_numpy(rng):
    """ref.py itself is validated against python-int ground truth."""
    p = field.P
    x = jnp.asarray(rng.integers(0, p, (60, 24)), jnp.int32)
    w = jnp.asarray(rng.integers(0, p, (24, 1, 2)), jnp.int32)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(2, 2, 4, 6, p), jnp.int32)
    got = np.asarray(ref.coded_grad_mc_ref(x, w, cbar, p))[:, 0].astype(object)
    xo = np.asarray(x).astype(object)
    wo = np.asarray(w)[:, 0].astype(object)
    z = (xo @ wo) % p
    s = (int(cbar[0]) + int(cbar[1]) * z[:, 0] + int(cbar[2]) * z[:, 0] * z[:, 1]) % p
    want = (xo.T @ s) % p
    assert (got == want).all()


def test_block_shape_invariance(rng):
    """Kernel output independent of BlockSpec tiling choices."""
    p = field.P
    a = jnp.asarray(rng.integers(0, p, (100, 200)), jnp.int32)
    b = jnp.asarray(rng.integers(0, p, (200, 70)), jnp.int32)
    outs = [np.asarray(modmatmul.modmatmul(a, b, p, bm=bm, bn=bn, bk=bk,
                                           interpret=True))
            for bm, bn, bk in [(32, 32, 64), (128, 128, 256), (16, 64, 32)]]
    assert all(np.array_equal(outs[0], o) for o in outs[1:])


def worker_oracle(x, w, cbar, p):
    """One worker's f(X̃, W̃) = X̃ᵀ ḡ(X̃ W̃) mod p in python ints:
    x (mk, d), w (d, c, r) -> (d, c)."""
    xo = np.asarray(x).astype(object)
    d, c, r = w.shape
    z = ((xo @ np.asarray(w).reshape(d, c * r).astype(object)) % p
         ).reshape(-1, c, r)
    s = np.full(z.shape[:2], int(cbar[0]), dtype=object)
    prod = None
    for i in range(r):
        prod = z[:, :, i] if prod is None else (prod * z[:, :, i]) % p
        s = (s + int(cbar[i + 1]) * prod) % p
    return (xo.T @ s) % p


def edge_values(p):
    """Where a balanced digit carries or the centring flips: 0, (p-1)/2,
    (p+1)/2, p-1, and 127 / 128 in every digit."""
    h = (p - 1) // 2
    v = [0, 1, h - 1, h, h + 1, p - 2, p - 1]
    for i in range(field.n_limbs(p)):
        v += [127 << 8 * i, 128 << 8 * i, (127 << 8 * i) + (128 << 8 * i)]
    v += [sum(127 << 8 * i for i in range(field.n_limbs(p))),
          sum(128 << 8 * i for i in range(field.n_limbs(p)))]
    return np.array(sorted({x % p for x in v}), np.int64)


@pytest.mark.parametrize("p", PRIMES, ids=["P24", "P30"])
def test_balanced_digits_recombine(p):
    """Every digit in [-128, 127], and sum d_i 2^{8i} == x mod p."""
    x = np.concatenate([edge_values(p),
                        np.random.default_rng(1).integers(0, p, 500)])
    ds = [np.asarray(t).astype(object)
          for t in coded_grad.digits(jnp.asarray(x, jnp.int32), p)]
    assert len(ds) == field.n_limbs(p)
    assert all(((-128 <= t) & (t <= 127)).all() for t in ds)
    back = sum(t * (1 << 8 * i) for i, t in enumerate(ds)) % p
    assert (back == x.astype(object)).all()


@pytest.mark.parametrize("p", PRIMES, ids=["P24", "P30"])
def test_offset_digits_recombine(p):
    """The share's int8 planes: sum u_i 2^{8i} + offset == x exactly."""
    nl = field.n_limbs(p)
    x = np.concatenate([edge_values(p),
                        np.random.default_rng(2).integers(0, p, 500)])
    us = coded_grad.offset_digits(jnp.asarray(x, jnp.int32), nl)
    assert len(us) == nl and all(u.dtype == jnp.int8 for u in us)
    back = sum(np.asarray(u).astype(object) * (1 << 8 * i)
               for i, u in enumerate(us)) + coded_grad.offset(nl)
    assert (back == x.astype(object)).all()


@pytest.mark.parametrize("p", PRIMES, ids=["P24", "P30"])
@pytest.mark.parametrize("c, r", [(1, 1), (1, 2), (10, 1), (10, 2)])
def test_coded_grad_workers_row_blocks(p, c, r, rng, monkeypatch):
    """Every worker of the stacked kernel == the python-int oracle, with
    mk = 300 rows in blocks of 128 (the last one overhangs the share) and
    d = 200, not a multiple of 128."""
    n, mk, d = 2, 300, 200
    monkeypatch.setattr(coded_grad, "BLOCK_BYTES", 128 * d * 4)
    assert coded_grad.row_block(mk, d) == 128
    x = jnp.asarray(rng.integers(0, p, (n, mk, d)), jnp.int32)
    w = jnp.asarray(rng.integers(0, p, (n, d, c, r)), jnp.int32)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(r, 2, 4, 6, p), jnp.int32)
    got = np.asarray(coded_grad.coded_grad_workers(x, w, cbar, p))
    assert got.shape == (n, d, c)
    for i in range(n):
        want = worker_oracle(x[i], w[i], cbar, p)
        assert (got[i].astype(object) == want).all(), f"worker {i}"


@pytest.mark.parametrize("p", PRIMES, ids=["P24", "P30"])
def test_coded_grad_edge_values(p, rng, monkeypatch):
    """Shares and weights made of the values where digits carry, in row
    blocks, == the python-int oracle."""
    mk, d, c, r = 200, 136, 2, 2
    monkeypatch.setattr(coded_grad, "BLOCK_BYTES", 128 * d * 4)
    e = edge_values(p)
    x = jnp.asarray(e[rng.integers(0, e.size, (1, mk, d))], jnp.int32)
    w = jnp.asarray(e[rng.integers(0, e.size, (1, d, c, r))], jnp.int32)
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(r, 2, 4, 6, p), jnp.int32)
    got = np.asarray(coded_grad.coded_grad_workers(x, w, cbar, p))[0]
    assert (got.astype(object) == worker_oracle(x[0], w[0], cbar, p)).all()


@pytest.mark.parametrize("worker_path", ["fused", "reference"],
                         indirect=True)
@pytest.mark.parametrize("c", [1, 3])
def test_all_worker_results_fused_equals_reference_vmap(c, worker_path,
                                                        rng):
    """N workers through all_worker_results on either path (the fused
    kernel takes the stack in one call) == the vmapped reference, and the
    path taken is counted."""
    from repro.obs.metrics import REGISTRY
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=c)
    x = jnp.asarray(rng.integers(0, cfg.p, (cfg.N, 40, 24)), jnp.int32)
    w = jnp.asarray(rng.integers(0, cfg.p, (cfg.N, 24, c, 1)), jnp.int32)
    cbar = jnp.asarray(protocol.poly_coeffs(cfg), jnp.int32)
    label = 'cpml_worker_programs{path="%s",nl="3",rows="40"}' % worker_path
    before = REGISTRY.counter(label).value
    got = np.asarray(protocol.all_worker_results(cfg, cbar, x, w))
    assert REGISTRY.counter(label).value == before + 1
    want = np.asarray(jax.vmap(
        lambda xi, wi: ref.coded_grad_mc_ref(xi, wi, cbar, cfg.p))(x, w))
    assert got.shape == (cfg.N, 24, c)
    assert np.array_equal(got, want)


def test_all_worker_results_fused_shard_on_four_devices():
    """The fused kernel under shard on four CPU devices (each device's
    N/D shares in one call) == the reference under vmap."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import protocol
from repro.core.protocol import compute
from repro.launch.mesh import auto_mesh
compute.fused = lambda: True
cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=2, backend="shard")
rng = np.random.default_rng(0)
x = jnp.asarray(rng.integers(0, cfg.p, (cfg.N, 40, 24)), jnp.int32)
w = jnp.asarray(rng.integers(0, cfg.p, (cfg.N, 24, 2, 1)), jnp.int32)
cbar = jnp.asarray(protocol.poly_coeffs(cfg), jnp.int32)
mesh = auto_mesh((4,), (cfg.mesh_axis,), devices=jax.devices()[:4])
with jax.set_mesh(mesh):
    got = protocol.all_worker_results(cfg, cbar, x, w)
compute.fused = lambda: False
jax.clear_caches()
ref_cfg = dataclasses.replace(cfg, backend="vmap")
want = protocol.all_worker_results(ref_cfg, cbar, x, w)
assert len(jax.devices()) == 4
print("SHARD_FUSED_OK", bool(np.array_equal(np.asarray(got), np.asarray(want))))
"""
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARD_FUSED_OK True" in out.stdout, out.stdout + out.stderr[-4000:]


@pytest.mark.parametrize("backend, want", [
    ("cpu", False), ("tpu", True), ("gpu", False)])
def test_worker_path_resolves_from_the_platform(monkeypatch, backend, want):
    """The worker step takes the fused kernel on a TPU and the reference
    elsewhere.  The path is counted, labelled, when a worker step is
    built."""
    from repro.core.protocol import compute
    from repro.obs.metrics import REGISTRY
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    assert compute.fused() is want
    label = 'cpml_worker_programs{path="%s",nl="3",rows="40"}' % (
        "fused" if want else "reference")
    before = REGISTRY.counter(label).value
    cbar = jnp.asarray(protocol.poly_coeffs(cfg), jnp.int32)
    jax.eval_shape(compute.worker_fn(cfg, cbar),
                   jax.ShapeDtypeStruct((40, 24), jnp.int32),
                   jax.ShapeDtypeStruct((24, 1, 1), jnp.int32))
    assert REGISTRY.counter(label).value == before + 1
