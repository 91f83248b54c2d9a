"""Optimizer, compression, checkpoint, resilience, data, sharding rules."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.loader import LMBatchLoader
from repro.optim import compress, optimizers as opt
from repro.runtime.resilience import (FailureInjector, HeartbeatMonitor,
                                      ResilientLoop)


# ----------------------------- optimizers ---------------------------------

def test_adamw_minimizes_quadratic(key):
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    cfg = opt.OptimizerConfig(learning_rate=0.1, warmup_steps=0,
                              total_steps=200, weight_decay=0.0)
    state = opt.init_state(cfg, params)
    for _ in range(150):
        grads = {"w": params["w"] - target}
        params, state, _ = opt.apply_updates(cfg, params, grads, state)
    assert float(jnp.abs(params["w"] - target).max()) < 0.15


def test_sgd_momentum(key):
    params = {"w": jnp.array([4.0])}
    cfg = opt.OptimizerConfig(name="sgd", learning_rate=0.05, warmup_steps=0,
                              momentum=0.9, grad_clip=100.0)
    state = opt.init_state(cfg, params)
    for _ in range(100):
        params, state, _ = opt.apply_updates(cfg, params, {"w": params["w"]},
                                             state)
    assert abs(float(params["w"][0])) < 0.2


def test_grad_clip():
    g = {"a": jnp.ones(4) * 100.0}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    assert abs(float(opt.global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) == pytest.approx(200.0)


def test_lr_schedule():
    cfg = opt.OptimizerConfig(learning_rate=1.0, warmup_steps=10,
                              total_steps=100)
    assert float(opt.lr_at(cfg, jnp.int32(5))) == pytest.approx(0.5, rel=0.01)
    assert float(opt.lr_at(cfg, jnp.int32(100))) == pytest.approx(0.1,
                                                                  rel=0.01)


def test_compression_unbiased(key):
    g = jax.random.normal(key, (2048,))
    acc = jnp.zeros_like(g)
    reps = 300
    for i in range(reps):
        q, s = compress.quantize_grad(jax.random.PRNGKey(i), g, bits=8)
        acc = acc + compress.dequantize_grad(q, s)
    err = float(jnp.abs(acc / reps - g).max())
    assert err < 0.02, err


def test_compress_tree_roundtrip(key):
    grads = {"a": jax.random.normal(key, (64,)),
             "b": {"c": jax.random.normal(key, (8, 8))}}
    q, s = compress.compress_tree(key, grads, bits=8)
    back = compress.decompress_tree(q, s)
    for x, y in zip(jax.tree.leaves(grads), jax.tree.leaves(back)):
        assert float(jnp.abs(x - y).max()) < 0.02


# ----------------------------- checkpoint ---------------------------------

def test_checkpoint_roundtrip(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "opt_state": {"step": jnp.int32(7)}}
    mgr.save(7, state)
    out = mgr.restore()
    assert out["step"] == 7
    assert np.allclose(out["params"]["w"], np.arange(6.0).reshape(2, 3))


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"params": {"w": jnp.ones(1) * s}})
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4
    assert float(mgr.restore()["params"]["w"][0]) == 4.0


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(1, {"params": {"w": jnp.zeros(4)}})
    mgr.wait()
    assert mgr.latest_step() == 1


def test_elastic_restore_with_sharding(tmp_path):
    """Restore places leaves with provided shardings (1-device 'mesh')."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("data",))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(2, {"params": {"w": jnp.ones((4, 4))}})
    sh = {"params": {"w": NamedSharding(mesh, P("data", None))}}
    out = mgr.restore(shardings=sh)
    assert out["params"]["w"].sharding == sh["params"]["w"]


# ----------------------------- resilience ---------------------------------

def test_heartbeat_survivors():
    mon = HeartbeatMonitor(6)
    for i in range(6):
        mon.heartbeat(i, latency_s=1.0)
    mon.mark_failed(2)
    mon.heartbeat(4, latency_s=50.0)   # straggler
    surv = mon.survivors()
    assert 2 not in surv and 4 not in surv
    assert len(surv) == 4


def test_failure_injection_deterministic():
    mon1, mon2 = HeartbeatMonitor(8), HeartbeatMonitor(8)
    for mon in (mon1, mon2):
        inj = FailureInjector(seed=3, fail_prob=0.2, straggle_prob=0.2)
        for _ in range(5):
            inj.step(mon)
    assert list(mon1.survivors()) == list(mon2.survivors())


def test_resilient_loop_restores(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(0, {"params": {"w": jnp.zeros(1)}})
    calls = {"n": 0}

    def step_fn(state, step):
        calls["n"] += 1
        if calls["n"] == 3:           # one transient failure
            raise RuntimeError("injected node failure")
        return {"params": {"w": state["params"]["w"] + 1}}

    loop = ResilientLoop(mgr, checkpoint_every=2, max_retries=2)
    out = loop.run({"params": {"w": jnp.zeros(1)}}, step_fn, 0, 4)
    assert loop.restarts == 1
    assert float(out["params"]["w"][0]) == 4.0   # replayed to completion


# ----------------------------- data ---------------------------------------

def test_loader_deterministic_and_shaped():
    with LMBatchLoader(None, batch=4, seq=16, vocab=100, seed=5) as l1, \
            LMBatchLoader(None, batch=4, seq=16, vocab=100, seed=5) as l2:
        b1, b2 = next(iter(l1)), next(iter(l2))
    assert b1["tokens"].shape == (4, 16)
    assert np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert np.array_equal(np.asarray(b1["tokens"][:, 1:]),
                          np.asarray(b1["labels"][:, :-1]))


def test_loader_close_joins_prefetch_thread():
    """close() must actually END the daemon producer — even when it is
    blocked on a full prefetch queue — and be idempotent."""
    loader = LMBatchLoader(None, batch=2, seq=8, vocab=50, prefetch=1)
    deadline = time.time() + 5.0
    while not loader._q.full() and time.time() < deadline:
        time.sleep(0.01)                 # producer now blocked in put()
    loader.close()
    assert not loader._thread.is_alive()
    loader.close()                       # idempotent


# ----------------------------- sharding rules ------------------------------

def test_divisible_or_replicate():
    from jax.sharding import PartitionSpec as P
    from repro.parallel import rules

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    mesh = FakeMesh()
    # divisible head dim -> sharded on model
    assert rules.spec_for(mesh, (2048, 4096), ("embed", "heads")) == \
        P("data", "model")
    # 25 heads stacked dim not divisible -> replicated
    assert rules.spec_for(mesh, (25, 64), ("heads", None)) == P()
    # odd vocab replicates, embed still sharded
    assert rules.spec_for(mesh, (32001, 1600), ("vocab", "embed")) == \
        P(None, "data")
    # batch over (pod, data) on multi-pod mesh
    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    assert rules.spec_for(PodMesh(), (256, 4096), ("batch", "seq")) == \
        P(("pod", "data"))
    # batch=1 cannot shard
    assert rules.spec_for(PodMesh(), (1, 4096), ("batch", "seq")) == P()
