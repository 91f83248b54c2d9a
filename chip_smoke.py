"""Bring-up smoke: the exact coded training path on a TPU at paper width.

    python chip_smoke.py             # one chip, every phase below
    python chip_smoke.py --chips 4   # only the shard backend across 4 chips

Runs paper Algorithm 1 through ``repro.core.protocol`` and the cluster
runtime at paper Case 1 width: N=40, K=13, T=1, r=1, (m, d) = (12396, 1568),
synthetic data from fixed seeds.  One chip:

  * worker step — ``all_worker_results`` through ``field.matmul`` and
    through the Pallas kernel at c=1 and c=10: bit-identical for all N
    workers, and equal to a python-int oracle for two of them;
  * training — the jitted scan with and without the kernel and the
    per-step ``train_reference``: bit-identical weights, and accuracy
    within 3 points of the cleartext baseline;
  * cluster runtime — ``ClusterRunner`` on the in-process transport under
    lognormal latency == ``train_reference`` replaying its responder trace.

``--chips 4`` trains with ``backend="shard"`` on a 4-device mesh (10
shares per chip, with and without the kernel) and with ``backend="vmap"``
on device 0: bit-identical.

Times printed on the way are information, not a benchmark.  The last line
of stdout is ``{"ok": true, "device": {...}}`` only when every check
passed; any failure raises and exits nonzero.  There is no CPU mode: JAX
silently falls back to the CPU when the TPU fails to initialise, so the
platform is checked before any work.  Everything runs in this one process,
which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# paper Case 1 (benchmarks/phases.py:case1) at the paper's (m, d)
N, R_DEG = 40, 1
M, D = 12396, 1568
# the paper's Fig. 3 count: after 5 iterations at this width the coded
# weights still trail the cleartext baseline by 17 points (52% vs 69%, on
# the chip and under highest matmul precision alike); after 25, by 0.5
ITERS = 25
CLUSTER_ROUNDS = 3
ACC_GAP = 0.03


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    print(f"  ok: {what}", flush=True)


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); there is no CPU mode")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    return devs


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0, out


def worker_oracle(x_share, w_share, cbar, p: int):
    """f(X̃ᵢ, W̃ᵢ) = X̃ᵢᵀ ḡ(X̃ᵢ W̃ᵢ) mod p in python ints (no overflow)."""
    import numpy as np
    xo = np.asarray(x_share).astype(object)               # (mk, d)
    d, c, r = w_share.shape
    z = (xo @ np.asarray(w_share).reshape(d, c * r).astype(object)) % p
    z = z.reshape(-1, c, r)
    s = np.full(z.shape[:2], int(cbar[0]), dtype=object)
    prod = None
    for i in range(1, r + 1):
        prod = z[:, :, i - 1] if prod is None else (prod * z[:, :, i - 1]) % p
        s = (s + int(cbar[i]) * prod) % p
    return (xo.T @ s) % p                                 # (d, c)


def worker_step_phase(cfg, x, c: int, seed: int = 0) -> None:
    """Kernel vs field.matmul worker results at c heads, plus the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import protocol

    cfg = dataclasses.replace(cfg, c=c)
    kx, kw, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    x_shares, _ = protocol.encode_dataset(cfg, kx, x)
    w = 0.1 * jax.random.normal(kw, (x.shape[1], c))
    w_shares = protocol.encode_weights(cfg, kq, w)        # (N, d, c, r)
    cbar = jnp.asarray(protocol.poly_coeffs(cfg))
    results = {}
    for use_kernel in (False, True):
        cfg_k = dataclasses.replace(cfg, use_kernel=use_kernel)
        fn = jax.jit(functools.partial(protocol.all_worker_results, cfg_k))
        t0 = time.perf_counter()
        compiled = fn.lower(cbar, x_shares, w_shares).compile()
        compile_s = time.perf_counter() - t0
        if use_kernel:
            # the kernel picks Mosaic exactly when the backend is not the CPU
            mosaic = "tpu_custom_call" in compiled.as_text()
            check(mosaic == (jax.default_backend() != "cpu"),
                  f"c={c}: kernel path compiled to Mosaic ({mosaic})")
        _, out = timed(compiled, cbar, x_shares, w_shares)
        steady = statistics.median(
            timed(compiled, cbar, x_shares, w_shares)[0] for _ in range(3))
        name = "kernel" if use_kernel else "field.matmul"
        print(f"  worker step c={c} [{name}]: compile {compile_s:.3f}s, "
              f"steady {steady:.6f}s for {cfg.N} workers", flush=True)
        results[use_kernel] = np.asarray(out)
    check(results[False].shape == (cfg.N, x.shape[1], c),
          f"c={c}: worker results shaped (N, d, c)")
    check(bool((results[False] == results[True]).all()),
          f"c={c}: kernel == field.matmul bit for bit, all {cfg.N} workers")
    xs, ws = np.asarray(x_shares), np.asarray(w_shares)
    for i in (0, cfg.N - 1):
        want = worker_oracle(xs[i], ws[i], np.asarray(cbar), cfg.p)
        check(bool((results[True][i].astype(object) == want).all()),
              f"c={c}: worker {i} == python-int oracle")


def round_timing(cfg, x, y, key) -> None:
    """Compile and steady seconds of one jitted protocol round."""
    import jax
    import jax.numpy as jnp

    from repro.core import protocol

    ksetup, kloop = jax.random.split(key)
    state = protocol.setup(cfg, ksetup, x, y)
    run = protocol.round_fn(cfg, state, protocol.lipschitz_eta(state.xq_real))
    dmat, order = protocol.survivor_round(cfg, None)
    w2 = jnp.zeros((x.shape[1], cfg.c), jnp.float32)
    args = (protocol.round_key(kloop, 0), w2, jnp.asarray(dmat),
            jnp.asarray(order))
    first, _ = timed(run, *args)
    steady = statistics.median(timed(run, *args)[0] for _ in range(3))
    print(f"  round [kernel={cfg.use_kernel}]: first call {first:.3f}s "
          f"(compile {first - steady:.3f}s), steady {steady:.6f}s/round",
          flush=True)


def training_phase(cfg, x, y, key) -> None:
    """Scan with and without the kernel == train_reference; accuracy."""
    import numpy as np

    from repro.core import protocol

    ws = {}
    for use_kernel in (True, False):
        cfg_k = dataclasses.replace(cfg, use_kernel=use_kernel)
        cold, (w, _) = timed(protocol.train, cfg_k, key, x, y, ITERS)
        warm, _ = timed(protocol.train, cfg_k, key, x, y, ITERS)
        print(f"  train {ITERS} iters [kernel={use_kernel}]: cold {cold:.3f}s,"
              f" warm {warm:.3f}s (setup included)", flush=True)
        ws[use_kernel] = np.asarray(w)
        round_timing(cfg_k, x, y, key)
    w_ref, _ = protocol.train_reference(cfg, key, x, y, ITERS)
    w_ref = np.asarray(w_ref)
    check(bool(np.isfinite(w_ref).all()) and w_ref.shape == (x.shape[1],),
          "weights finite, shaped (d,)")
    check(bool((ws[True] == ws[False]).all()),
          "scan with kernel == scan without, bit for bit")
    check(bool((ws[False] == w_ref).all()),
          "scan == train_reference, bit for bit")
    wc, xq = protocol.cleartext_baseline(cfg, x, y, ITERS)
    _, acc = protocol.loss_and_accuracy(w_ref, xq, y)
    _, acc_ref = protocol.loss_and_accuracy(wc, xq, y)
    print(f"  accuracy: coded {float(acc):.4f} vs cleartext baseline "
          f"{float(acc_ref):.4f}", flush=True)
    check(abs(float(acc) - float(acc_ref)) <= ACC_GAP,
          f"coded accuracy within {ACC_GAP:.0%} of cleartext")


def cluster_phase(cfg, x, y, key) -> None:
    """In-process ClusterRunner == train_reference on its responder trace."""
    import numpy as np

    from repro.cluster import ClusterRunner, make_latency
    from repro.core import protocol

    runner = ClusterRunner(cfg, key, x, y, make_latency("lognormal", seed=0))
    t, w = timed(runner.run, CLUSTER_ROUNDS)
    print(f"  cluster {CLUSTER_ROUNDS} rounds (lognormal, in-process): "
          f"{t:.3f}s wall", flush=True)
    w_ref, _ = protocol.train_reference(runner.cfg, key, x, y,
                                        CLUSTER_ROUNDS,
                                        survivor_fn=runner.survivor_fn())
    check(bool((np.asarray(w) == np.asarray(w_ref)).all()),
          "ClusterRunner == train_reference on its responder trace")


def shard_phase(cfg, x, y, key, chips: int) -> None:
    """Shard backend across ``chips`` devices == vmap on device 0."""
    import jax
    import numpy as np

    from repro.core import protocol
    from repro.launch.mesh import auto_mesh

    cold, (wv, _) = timed(protocol.train, cfg, key, x, y, ITERS)
    print(f"  vmap on {jax.devices()[0]}: cold {cold:.3f}s", flush=True)
    mesh = auto_mesh((chips,), (cfg.mesh_axis,),
                     devices=jax.devices()[:chips])
    for use_kernel in (False, True):
        cfg_s = dataclasses.replace(cfg, backend="shard",
                                    use_kernel=use_kernel)
        with jax.set_mesh(mesh):
            cold, (w, _) = timed(protocol.train, cfg_s, key, x, y, ITERS)
            warm, _ = timed(protocol.train, cfg_s, key, x, y, ITERS)
        print(f"  shard x{chips} [kernel={use_kernel}]: cold {cold:.3f}s, "
              f"warm {warm:.3f}s", flush=True)
        check(bool((np.asarray(w) == np.asarray(wv)).all()),
              f"shard over {chips} devices ({cfg.N // chips} shares each, "
              f"kernel={use_kernel}) == vmap, bit for bit")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the shard backend across four chips "
                         "against vmap")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    import jax

    from repro.core import protocol
    from repro.data import synthetic
    from repro.launch import device

    print(device.device_line(), flush=True)
    device.enable_compile_cache()
    cfg = protocol.CPMLConfig(N=N, K=(N - 1) // (2 * R_DEG + 1), T=1, r=R_DEG)
    print(f"Case 1: N={cfg.N} K={cfg.K} T={cfg.T} r={cfg.r} m={M} d={D} "
          f"threshold={cfg.threshold}", flush=True)
    x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=M, d=D, margin=12.0)
    key = jax.random.PRNGKey(7)
    t0 = time.perf_counter()
    if args.chips == 1:
        for c in (1, 10):
            print(f"[worker step c={c}]", flush=True)
            worker_step_phase(cfg, x, c)
        print("[training]", flush=True)
        training_phase(cfg, x, y, key)
        print("[cluster runtime]", flush=True)
        cluster_phase(cfg, x, y, key)
    else:
        print(f"[shard x{args.chips}]", flush=True)
        shard_phase(cfg, x, y, key, args.chips)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    stats = devs[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak bytes in use on {devs[0]}: {stats['peak_bytes_in_use']}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
