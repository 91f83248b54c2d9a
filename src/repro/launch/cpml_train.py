"""CodedPrivateML training driver (the coded-workload analogue of launch.train).

    python -m repro.launch.cpml_train --classes 10 --iters 25 --batch-rows 64

Builds a synthetic classification task, runs the scan-jitted coded engine
(multi-class one-vs-all + optional mini-batch SGD + optional straggler
schedule), and reports accuracy against the cleartext quantized baseline.
``--backend shard`` spreads the N workers over every device JAX sees, a
block of N/D coded shares per device (the paper's deployment shape at
D = N); the worker step runs the fused Pallas kernel on a TPU and the jnp
reference elsewhere, and ``--kernel`` forces the kernel.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="CodedPrivateML coded training")
    ap.add_argument("--workers", "-N", type=int, default=8)
    ap.add_argument("--parallel", "-K", type=int, default=2)
    ap.add_argument("--privacy", "-T", type=int, default=1)
    ap.add_argument("--degree", "-r", type=int, default=1)
    ap.add_argument("--classes", "-c", type=int, default=1,
                    help="1 = binary logistic regression (the paper's task)")
    ap.add_argument("--m", type=int, default=2000, help="samples")
    ap.add_argument("--d", type=int, default=128, help="features")
    ap.add_argument("--iters", type=int, default=25)
    ap.add_argument("--eta", type=float, default=None,
                    help="step size (default: 1/L via power iteration)")
    ap.add_argument("--batch-rows", type=int, default=None,
                    help="mini-batch rows per part per round (default: full)")
    ap.add_argument("--backend", choices=("vmap", "shard"), default="vmap")
    ap.add_argument("--kernel", action="store_true",
                    help="force the fused Pallas worker kernel (default: "
                         "on a TPU only)")
    ap.add_argument("--p30", action="store_true",
                    help="use the 30-bit extended prime (more headroom)")
    ap.add_argument("--drop-workers", type=int, default=0,
                    help="simulate this many stragglers every round")
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json-out", type=str, default=None,
                    help="write the final metrics to this path")
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    import jax
    import numpy as np

    from repro.core import field, protocol
    from repro.data import synthetic
    from repro.launch import device
    from repro.launch.mesh import auto_mesh

    print(device.device_line())
    device.enable_compile_cache()

    cfg = protocol.CPMLConfig(
        N=args.workers, K=args.parallel, T=args.privacy, r=args.degree,
        c=args.classes, p=field.P30 if args.p30 else field.P,
        backend=args.backend, use_kernel=True if args.kernel else None,
        batch_rows=args.batch_rows)
    drop = args.drop_workers
    assert cfg.N - drop >= cfg.threshold, (
        f"dropping {drop} of N={cfg.N} leaves fewer than the recovery "
        f"threshold {cfg.threshold}")
    print(f"CPML: N={cfg.N} K={cfg.K} T={cfg.T} r={cfg.r} c={cfg.c} "
          f"threshold={cfg.threshold} backend={cfg.backend} "
          f"kernel={cfg.use_kernel} batch_rows={cfg.batch_rows}")

    key = jax.random.PRNGKey(args.seed)
    if cfg.c == 1:
        x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=args.m, d=args.d,
                                    margin=12.0)
    else:
        x, y = synthetic.multiclass_mnist_like(jax.random.PRNGKey(1),
                                               m=args.m, d=args.d, c=cfg.c)

    survivor_fn = None
    if drop:
        survivor_fn = lambda t: np.roll(np.arange(cfg.N), t)[: cfg.N - drop]

    def run():
        return protocol.train(cfg, key, x, y, iters=args.iters, eta=args.eta,
                              survivor_fn=survivor_fn,
                              eval_every=args.eval_every)

    t0 = time.time()
    if args.backend == "shard":
        mesh = auto_mesh((jax.device_count(),), (cfg.mesh_axis,))
        with jax.set_mesh(mesh):
            w, hist = run()
    else:
        w, hist = run()
    dt = time.time() - t0
    for h in hist:
        print(f"  iter {h['iter']:4d}  loss {h['loss']:.4f}  "
              f"acc {h['acc']:.2%}")
    print(f"trained {args.iters} private iterations in {dt:.1f}s "
          f"({args.iters / dt:.1f} it/s, one jitted scan)")

    # cleartext quantized baseline: same X̄, true sigmoid, same step count
    wc, xq = protocol.cleartext_baseline(cfg, x, y, args.iters, eta=args.eta)
    if cfg.c == 1:
        _, acc_ref = protocol.loss_and_accuracy(wc, xq, y)
        _, acc = protocol.loss_and_accuracy(w, xq, y)
    else:
        _, acc_ref = protocol.multiclass_loss_and_accuracy(wc, xq, y)
        _, acc = protocol.multiclass_loss_and_accuracy(w, xq, y)
    print(f"accuracy: coded {float(acc):.2%} vs cleartext baseline "
          f"{float(acc_ref):.2%}")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"config": {"N": cfg.N, "K": cfg.K, "T": cfg.T,
                                  "r": cfg.r, "c": cfg.c,
                                  "backend": cfg.backend,
                                  "use_kernel": cfg.use_kernel,
                                  "batch_rows": cfg.batch_rows},
                       "seconds": dt, "history": hist,
                       "acc_coded": float(acc),
                       "acc_cleartext": float(acc_ref)}, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
