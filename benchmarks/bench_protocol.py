"""Protocol micro-benchmark: worker-step throughput per compute backend.

Times one full worker round (encode weights -> all N worker polynomials ->
survivor decode) for the vmap and shard backends, and the fused-vs-unfused
worker computation, across (K, T, r, c) settings.  Emits CSV rows (see
benchmarks/common.py) and writes BENCH_protocol.json so future PRs have a
perf trajectory.

Fused-kernel caveat (DESIGN.md §4): on CPU there is no Mosaic compiler —
Pallas ``interpret=True`` is a correctness simulator, orders of magnitude
slower than anything, so timing it says nothing about the TPU kernel.  On
CPU the fused path is therefore timed via its jnp fallback and the JSON
records ``"fused_backend": "jnp-fallback"``; on a TPU host the same script
times the real Mosaic kernel (``"fused_backend": "pallas"``).

    PYTHONPATH=src python benchmarks/bench_protocol.py [--out BENCH_protocol.json]
"""
from __future__ import annotations

import argparse
import json
import os

# one host device per worker so the shard backend is a real 8-way mesh;
# must happen before jax initializes.
N_WORKERS = 8
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_WORKERS}")

import jax
import jax.numpy as jnp
import numpy as np

from common import emit, time_fn

from repro.core import protocol, sigmoid_poly
from repro.kernels import ops as kernel_ops
from repro.launch.mesh import auto_mesh

# (K, T, r, c) sweeps at N=8; threshold (2r+1)(K+T-1)+1 must stay <= 8.
DEFAULT_SETTINGS = [
    (2, 1, 1, 1),    # the paper's binary Case 2 at N=8
    (2, 1, 1, 4),    # multi-class amortization over the same shares
    (2, 1, 1, 10),
    (3, 0, 1, 4),    # more parallelism, no privacy masks
]
DEFAULT_M, DEFAULT_D = 1024, 256


def bench_setting(K: int, T: int, r: int, c: int, m: int, d: int,
                  mesh) -> dict:
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (m, d))
    cbar = jnp.asarray(sigmoid_poly.quantized_coeffs(r, 2, 4, 6), jnp.int32)
    w = jnp.zeros((d,) if c == 1 else (d, c), jnp.float32)
    entry = {"N": N_WORKERS, "K": K, "T": T, "r": r, "c": c,
             "backends_us": {}}

    def round_fn(cfg):
        state = protocol.setup(cfg, key, x, jnp.zeros((m,)))
        dmat = protocol.make_decode_matrix(cfg, np.arange(cfg.threshold))
        order = jnp.arange(cfg.threshold, dtype=jnp.int32)

        @jax.jit
        def one_round(k, wv):
            w_shares = protocol.encode_weights(cfg, k, wv)
            res = protocol.all_worker_results(cfg, cbar, state.x_shares,
                                              w_shares)
            return protocol.decode_gradient(cfg, jnp.take(res, order, 0), dmat)

        return one_round

    for backend in ("vmap", "shard"):
        cfg = protocol.CPMLConfig(N=N_WORKERS, K=K, T=T, r=r, c=c,
                                  backend=backend)
        fn = round_fn(cfg)
        if backend == "shard":
            with jax.set_mesh(mesh):
                us = time_fn(fn, key, w)
        else:
            us = time_fn(fn, key, w)
        entry["backends_us"][backend] = us
        rows = m // K * K
        emit(f"protocol_round/{backend}/K{K}_T{T}_r{r}_c{c}", us,
             f"{rows * c / (us / 1e6):.3e} row-heads/s")

    # fused vs unfused worker computation (ONE worker's share)
    mk = m // K
    rng = np.random.default_rng(0)
    p = cfg.p
    xs = jnp.asarray(rng.integers(0, p, (mk, d)), jnp.int32)
    ws = jnp.asarray(rng.integers(0, p, (d, c, r)), jnp.int32)
    pallas_ok = jax.default_backend() != "cpu"

    def unfused(a, b):
        return kernel_ops.coded_grad_mc(a, b, cbar, p, use_pallas=False)

    def fused(a, b):
        return kernel_ops.coded_grad_mc(a, b, cbar, p, use_pallas=pallas_ok)

    entry["worker_unfused_us"] = time_fn(unfused, xs, ws, warmup=2, iters=5)
    entry["worker_fused_us"] = time_fn(fused, xs, ws, warmup=2, iters=5)
    entry["fused_backend"] = "pallas" if pallas_ok else "jnp-fallback"
    entry["fused_not_slower"] = bool(
        entry["worker_fused_us"] <= entry["worker_unfused_us"] * 1.15)
    emit(f"worker_fused/K{K}_T{T}_r{r}_c{c}", entry["worker_fused_us"],
         f"vs unfused {entry['worker_unfused_us']:.1f}us "
         f"({entry['fused_backend']})")
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_protocol.json"))
    ap.add_argument("--m", type=int, default=DEFAULT_M)
    ap.add_argument("--d", type=int, default=DEFAULT_D)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + the first two settings (CI tier-1:"
                         " exercises the full bench path and enforces the "
                         "acceptance flags on every push)")
    args = ap.parse_args(argv)

    settings_sweep = DEFAULT_SETTINGS
    if args.smoke:
        settings_sweep = DEFAULT_SETTINGS[:2]
        if args.m == DEFAULT_M:
            args.m = 256
        if args.d == DEFAULT_D:
            args.d = 64
    mesh = auto_mesh((N_WORKERS,), ("workers",))
    settings = [bench_setting(K, T, r, c, args.m, args.d, mesh)
                for (K, T, r, c) in settings_sweep]
    report = {
        "device": jax.default_backend(),
        "pallas_compiled": jax.default_backend() != "cpu",
        "shapes": {"m": args.m, "d": args.d, "N": N_WORKERS},
        "smoke": args.smoke,
        "settings": settings,
        "kernel_not_slower": bool(all(s["fused_not_slower"]
                                      for s in settings)),
    }
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out}  kernel_not_slower={report['kernel_not_slower']}")
    # the acceptance flags gate CI: a fused kernel that got slower than its
    # unfused oracle (beyond the 1.15x noise headroom) fails the job
    return 0 if report["kernel_not_slower"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
