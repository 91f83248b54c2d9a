"""The dataset encode placed by worker under the shard backend.

Each device computes and keeps only its own N/D shares, row block by row
block, bit-identical to the one-program encode; a job trained so equals
the vmap backend bit for bit and the benchmark's plain reference exactly.
Multi-device cases run in subprocesses with four forced CPU devices.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field, lagrange
from repro.core.protocol import CPMLConfig, encode, engine

REPO = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(REPO, "src")


def _run(code: str, marker: str) -> None:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([SRC, REPO]),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert marker in out.stdout, out.stdout + out.stderr[-4000:]


@pytest.mark.parametrize("p", [field.P, field.P30], ids=["P24", "P30"])
def test_combine_block_is_those_rows_of_the_whole(p):
    scheme = lagrange.CodingScheme(8, 3, 1, p)
    flat = jax.random.randint(jax.random.PRNGKey(1), (4, 37), 0, p,
                              dtype=jnp.int32)
    whole = lagrange.combine(scheme.encode_matrix, flat, p)

    @jax.jit
    def block(i):
        return lagrange.combine(scheme.encode_matrix, flat, p, block=(i, 2))

    for i in range(4):
        np.testing.assert_array_equal(block(i), whole[2 * i: 2 * i + 2])


def test_sharded_encode_is_bit_identical_and_placed_by_worker():
    _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import field, protocol
from repro.core.protocol import encode
from repro.launch.mesh import auto_mesh
from repro.obs import REGISTRY

m, d = 203, 16                      # m % K != 0 for K = 2 and K = 3
x = jax.random.uniform(jax.random.PRNGKey(1), (m, d))
blocks = REGISTRY.counter("cpml_encode_row_blocks")
for N, K, p in ((8, 2, field.P), (12, 3, field.P30)):
    cfgv = protocol.CPMLConfig(N=N, K=K, T=1, r=1, p=p)
    cfgs = protocol.CPMLConfig(N=N, K=K, T=1, r=1, p=p, backend="shard")
    mk = -(-m // K)
    # 7 rows a block: ceil(mk / 7) blocks, the last one started early
    encode.ENCODE_BLOCK_BYTES = (K + 1) * d * 4 * 7
    key = jax.random.PRNGKey(7)
    sv, cv = protocol.encode_dataset(cfgv, key, x)
    before = blocks.value
    with jax.set_mesh(auto_mesh((4,), ("workers",))):
        ss, cs = protocol.encode_dataset(cfgs, key, x)
    assert blocks.value - before == -(-mk // 7) >= 2
    n = N // 4
    assert [s.data.shape for s in ss.addressable_shards] == [(n, mk, d)] * 4
    assert sorted(s.index[0].start for s in ss.addressable_shards) == \
        [0, n, 2 * n, 3 * n]
    assert (np.asarray(sv) == np.asarray(ss)).all()
    assert (np.asarray(cv["xq"]) == np.asarray(cs["xq"])).all()
    assert REGISTRY.gauge("cpml_share_bytes_per_chip").value == n * mk * d * 4
print("ENCODE_OK")
""", "ENCODE_OK")


def test_shard_train_equals_vmap_and_the_reference_at_p30():
    """A dense epsilon-like task at P30: the shard backend's job equals the
    vmap backend's bit for bit and the plain reference exactly; mini-batch
    rounds read the sharded shares too."""
    _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, jax, numpy as np
from repro.core import field, protocol
from repro.core.protocol import encode
from repro.launch.mesh import auto_mesh
from bench import check, data
from bench.references import logistic

config = dict(N=12, K=3, T=1, r=1, c=1, lx=2, lw=4, lc=6, p=field.P30,
              m=301, d=24, sigmoid_fit=[-4.0, 4.0, 2001])
x, y = data.mnist_like(jax.random.PRNGKey(11), m=301, d=24, sparsity=0.0,
                       margin=10.0)
encode.ENCODE_BLOCK_BYTES = 4 * 24 * 4 * 9
cfgv = protocol.CPMLConfig(**{k: config[k] for k in
                              ("N", "K", "T", "r", "c", "lx", "lw", "lc",
                               "p")})
cfgs = dataclasses.replace(cfgv, backend="shard")
mesh = auto_mesh((4,), ("workers",))
for j in range(2):
    key = jax.random.PRNGKey(100 + j)
    wv, _ = protocol.train(cfgv, key, x, y, iters=20)
    with jax.set_mesh(mesh):
        ws, _ = protocol.train(cfgs, key, x, y, iters=20)
    assert (np.asarray(wv) == np.asarray(ws)).all()
    w_ref = logistic.train(config, x, y, key, 20)
    assert check.rel_err(ws, w_ref) == 0.0, check.rel_err(ws, w_ref)
bv = dataclasses.replace(cfgv, batch_rows=16)
bs = dataclasses.replace(cfgs, batch_rows=16)
wv, _ = protocol.train(bv, jax.random.PRNGKey(5), x, y, iters=6)
with jax.set_mesh(mesh):
    ws, _ = protocol.train(bs, jax.random.PRNGKey(5), x, y, iters=6)
assert (np.asarray(wv) == np.asarray(ws)).all()
print("TRAIN_OK")
""", "TRAIN_OK")


def test_full_batch_setup_holds_the_cleartext_once():
    x = jax.random.uniform(jax.random.PRNGKey(0), (41, 6))
    y = (x[:, 0] > 0.5).astype(jnp.float32)
    cfg = CPMLConfig(N=8, K=2, T=1, r=1)
    state = engine.setup(cfg, jax.random.PRNGKey(1), x, y)
    assert state.xq_parts is None and state.y_parts is None
    assert state.xq_real.shape == (42, 6)
    mb = engine.setup(CPMLConfig(N=8, K=2, T=1, r=1, batch_rows=4),
                      jax.random.PRNGKey(1), x, y)
    np.testing.assert_array_equal(mb.xq_parts.reshape(42, 6), mb.xq_real)
    assert mb.y_parts.shape == (2, 21, 1)
    np.testing.assert_array_equal(mb.xty, state.xty)


@pytest.mark.parametrize("c", [1, 10])
def test_xty_in_one_program_is_the_eager_transpose_product(c):
    """Xᵀy without a transposed copy of the dataset: the same values as
    the eager transpose and product, at P30 on dense rows."""
    x = jax.random.uniform(jax.random.PRNGKey(2), (3001, 40))
    labels = jax.random.randint(jax.random.PRNGKey(3), (3001,), 0, c)
    y = labels.astype(jnp.float32) if c == 1 else labels
    cfg = CPMLConfig(N=12, K=3, T=1, r=1, c=c, p=field.P30)
    state = engine.setup(cfg, jax.random.PRNGKey(4), x, y)
    targets = engine._targets(cfg, state.y)
    eager = state.xq_real.T @ targets
    np.testing.assert_array_equal(engine._xty(state.xq_real, targets), eager)
    np.testing.assert_array_equal(state.xty, engine._w_public(cfg, eager))


def test_the_evaluation_copy_is_made_only_when_evaluating(monkeypatch):
    seen = []
    scan = engine._train_scan

    def spy(cfg, eval_every, *args):
        seen.append((eval_every, args[-2] is None, args[-1] is None))
        return scan(cfg, eval_every, *args)

    monkeypatch.setattr(engine, "_train_scan", spy)
    x = jax.random.uniform(jax.random.PRNGKey(0), (40, 6))
    y = (x[:, 0] > 0.5).astype(jnp.float32)
    cfg = CPMLConfig(N=8, K=2, T=1, r=1)
    engine.train(cfg, jax.random.PRNGKey(1), x, y, iters=2)
    _, hist = engine.train(cfg, jax.random.PRNGKey(1), x, y, iters=2,
                           eval_every=1)
    assert seen == [(0, True, True), (1, False, False)]
    assert len(hist) == 2


def test_epsilon_needs_the_30_bit_prime():
    """400,000 rows at K=13 are 30,770 a part: the paper's 24-bit prime
    can wrap in the worst case, P30 cannot."""
    case1 = dict(N=40, K=13, T=1, r=1)
    assert CPMLConfig(**case1).headroom_bits(1.0, 400000) < 0
    assert CPMLConfig(**case1, p=field.P30).headroom_bits(1.0, 400000) > 0


def test_block_rows_cover_each_part_in_blocks_of_one_shape():
    cfg = CPMLConfig(N=40, K=13, T=1, r=1, p=field.P30)
    mk, d = 30770, 2000
    rows = encode.block_rows(cfg, mk, d)
    assert (cfg.K + cfg.T) * rows * d * 4 <= encode.ENCODE_BLOCK_BYTES
    assert 1 <= rows < mk
    assert encode.block_rows(cfg, 5, d) == 5
