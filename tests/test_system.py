"""End-to-end system behaviour: drivers, paper-reproduction invariants."""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_train_driver_reduced(tmp_path):
    from repro.launch import train
    rc = train.main(["--arch", "tinyllama-1.1b", "--reduced", "--steps", "8",
                     "--batch", "4", "--seq", "32", "--log-every", "100",
                     "--checkpoint-dir", str(tmp_path)])
    assert rc == 0


def test_train_driver_resume(tmp_path):
    from repro.launch import train
    train.main(["--arch", "tinyllama-1.1b", "--reduced", "--steps", "6",
                "--batch", "2", "--seq", "32", "--checkpoint-every", "3",
                "--checkpoint-dir", str(tmp_path), "--log-every", "100"])
    rc = train.main(["--arch", "tinyllama-1.1b", "--reduced", "--steps", "3",
                     "--batch", "2", "--seq", "32", "--resume",
                     "--checkpoint-dir", str(tmp_path), "--log-every", "100"])
    assert rc == 0


def test_serve_driver_reduced():
    from repro.launch import serve
    rc = serve.main(["--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
                     "--prompt-len", "16", "--gen", "4"])
    assert rc == 0


def test_serve_coded_head_with_failure():
    from repro.launch import serve
    rc = serve.main(["--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
                     "--prompt-len", "16", "--coded-head", "--coded-k", "4",
                     "--coded-t", "1", "--coded-n", "6", "--kill-shard", "3"])
    assert rc == 0


def test_paper_accuracy_reproduction():
    """Fig. 3-style: CPML accuracy ~= conventional logistic regression on a
    separable MNIST-like task after 25 iterations (small scale for CI)."""
    from repro.core import protocol
    from repro.data import synthetic
    x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=800, d=60,
                                margin=12.0)
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    w, hist = protocol.train(cfg, jax.random.PRNGKey(7), x, y, iters=25,
                             eval_every=25)
    # uncoded reference
    state = protocol.setup(cfg, jax.random.PRNGKey(7), x, y)
    eta = protocol.lipschitz_eta(state.xq_real)
    w2 = jnp.zeros(x.shape[1])
    xq = state.xq_real[:800]
    for _ in range(25):
        w2 = w2 - eta * (xq.T @ (protocol.sigmoid(xq @ w2) - y)) / 800
    _, acc_ref = protocol.loss_and_accuracy(w2, xq, y)
    acc_coded = hist[-1]["acc"]
    assert acc_coded > 0.8
    assert abs(acc_coded - float(acc_ref)) < 0.03


def test_cpml_train_driver(tmp_path, capsys):
    """The coded-workload CLI end to end: multi-class + mini-batch + a
    straggler every round, json metrics out.  Its first line names the
    device it ran on."""
    from repro.launch import cpml_train
    out = tmp_path / "cpml.json"
    rc = cpml_train.main(["--classes", "3", "--m", "300", "--d", "24",
                          "--iters", "4", "--eval-every", "2",
                          "--batch-rows", "32", "--drop-workers", "1",
                          "--json-out", str(out)])
    assert rc == 0
    first = capsys.readouterr().out.splitlines()[0]
    assert first.startswith("device: platform=cpu kind="), first
    import json
    rep = json.loads(out.read_text())
    assert rep["config"]["c"] == 3 and len(rep["history"]) == 2
    assert 0.0 <= rep["acc_coded"] <= 1.0


@pytest.mark.slow
def test_shard_map_backend_multidevice():
    """CPML 'shard' backend on an 8-device forced-CPU mesh == vmap backend."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import protocol
from repro.data import synthetic
from repro.launch.mesh import auto_mesh

x, y = synthetic.mnist_like(jax.random.PRNGKey(42), m=400, d=30)
mesh = auto_mesh((8,), ("workers",))
cfgv = protocol.CPMLConfig(N=8, K=2, T=1, r=1, backend="vmap")
sv = protocol.setup(cfgv, jax.random.PRNGKey(0), x, y)
wv = protocol.step(cfgv, jax.random.PRNGKey(1), sv, 0.5).w
cfgs = protocol.CPMLConfig(N=8, K=2, T=1, r=1, backend="shard")
ss = protocol.setup(cfgs, jax.random.PRNGKey(0), x, y)
with jax.set_mesh(mesh):
    ws = protocol.step(cfgs, jax.random.PRNGKey(1), ss, 0.5).w
assert np.allclose(np.asarray(wv), np.asarray(ws), atol=1e-6), \
    float(jnp.abs(wv - ws).max())
# scan engine == per-step reference loop, bit-identical, on the shard
# backend — with and without the fused worker kernel (acceptance matrix).
for kern in (False, True):
    cfgk = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, backend="shard",
                               use_kernel=kern)
    xm, ym = synthetic.multiclass_mnist_like(jax.random.PRNGKey(2), m=240,
                                             d=24, c=3)
    with jax.set_mesh(mesh):
        w1, _ = protocol.train(cfgk, jax.random.PRNGKey(5), xm, ym, iters=10)
        w2, _ = protocol.train_reference(cfgk, jax.random.PRNGKey(5), xm, ym,
                                         iters=10)
    assert (np.asarray(w1) == np.asarray(w2)).all(), kern
print("SHARD_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARD_OK" in out.stdout, out.stdout + out.stderr


def test_shard_backend_blocks_of_shares():
    """N=8 workers on 4 devices: each device evaluates a block of 2 shares,
    and training is bit-identical to vmap.  A device count that does not
    divide N is refused with a clear error."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core import protocol
from repro.data import synthetic
from repro.launch.mesh import auto_mesh

x, y = synthetic.mnist_like(jax.random.PRNGKey(3), m=240, d=24)
cfgv = protocol.CPMLConfig(N=8, K=2, T=1, r=1, backend="vmap")
wv, _ = protocol.train(cfgv, jax.random.PRNGKey(5), x, y, iters=4)
cfgs = protocol.CPMLConfig(N=8, K=2, T=1, r=1, backend="shard")
with jax.set_mesh(auto_mesh((4,), ("workers",))):
    ws, _ = protocol.train(cfgs, jax.random.PRNGKey(5), x, y, iters=4)
assert (np.asarray(wv) == np.asarray(ws)).all()
with jax.set_mesh(auto_mesh((3,), ("workers",), devices=jax.devices()[:3])):
    try:
        protocol.train(cfgs, jax.random.PRNGKey(5), x, y, iters=1)
    except ValueError as e:
        assert "do not divide N=8" in str(e), e
    else:
        raise AssertionError("3 devices for N=8 was not refused")
print("BLOCKS_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "BLOCKS_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """The real dry-run path (512 host devices, production mesh) for the
    smallest arch — proves lower+compile+analysis works end to end."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "train_4k", "--out", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert "ok=1" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
