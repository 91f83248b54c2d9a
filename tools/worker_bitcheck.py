"""The fused worker kernel against the jnp reference, at each benchmark
cell's share shapes, on the chip.

    python tools/worker_bitcheck.py

For each shape, uniform field elements (with the values where balanced
digits carry written into the first rows) go through
``compute.workers_fn`` forced to the fused kernel and to the reference; all
N workers' results must be equal bit for bit.  The epsilon cell's shares
are a chip's 10 at a time, four times over.  Prints each path's steady
seconds and the compiled program's temporary bytes (a copy of the shares
before the kernel shows there), then ``cpml_worker_programs``.  The last
line is ``{"ok": true, ...}`` only when every check passed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import field, protocol  # noqa: E402
from repro.core.protocol import compute  # noqa: E402
from repro.obs.metrics import REGISTRY  # noqa: E402

CASE1 = dict(N=40, K=13, T=1, r=1)
# (name, workers a call, calls, mk, d, c, p)
SHAPES = [("case1-mnist37", 40, 1, 954, 1568, 1, field.P),
          ("case1-mnist10-p30", 40, 1, 4616, 784, 10, field.P30),
          ("case1-epsilon-p30, a chip's 10", 10, 4, 30770, 2000, 1,
           field.P30)]


def edges(p: int) -> np.ndarray:
    """Values where a balanced digit carries or the centring flips."""
    h = (p - 1) // 2
    v = [0, 1, h - 1, h, h + 1, h + 2, p - 2, p - 1]
    for i in range(field.n_limbs(p)):
        v += [127 << 8 * i, 128 << 8 * i, 255 << 8 * i]
    return np.array([x % p for x in v], np.int32)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0, out


def check_shape(name, n, calls, mk, d, c, p, seed=0) -> dict:
    cfg = protocol.CPMLConfig(**CASE1, c=c, p=p)
    cbar = jnp.asarray(protocol.poly_coeffs(cfg), jnp.int32)
    fns = {k: jax.jit(compute.workers_fn(
        dataclasses.replace(cfg, use_kernel=k), cbar))
        for k in (True, False)}
    e = jnp.asarray(edges(p))
    out = {"shape": [n * calls, mk, d, c], "p": p, "equal": True}
    for call in range(calls):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed + call))
        x = jax.random.randint(kx, (n, mk, d), 0, p, jnp.int32)
        x = x.at[:, : e.size, : e.size].set(
            jnp.broadcast_to(e[:, None], (n, e.size, e.size)))
        w = jax.random.randint(kw, (n, d, c, cfg.r), 0, p, jnp.int32)
        w = w.at[:, : e.size, 0, 0].set(e)
        res = {}
        for k, fn in fns.items():
            if call == 0:
                comp = fn.lower(x, w).compile()
                out[f"temp_bytes_{'fused' if k else 'reference'}"] = int(
                    comp.memory_analysis().temp_size_in_bytes)
            _, res[k] = timed(fn, x, w)
            if call == 0:
                out[f"s_{'fused' if k else 'reference'}"] = statistics.median(
                    timed(fn, x, w)[0] for _ in range(5))
        equal = bool((np.asarray(res[True]) == np.asarray(res[False])).all())
        out["equal"] &= equal
        del x, w, res
    print(f"{name}: {json.dumps(out)}", flush=True)
    return out


def main() -> int:
    from repro.launch import device
    print(device.device_line(), flush=True)
    if jax.default_backend() != "tpu":
        raise SystemExit("worker_bitcheck: no TPU")
    results = {s[0]: check_shape(*s) for s in SHAPES}
    counters = {k: v["value"] for k, v in REGISTRY.snapshot().items()
                if k.startswith("cpml_worker_programs")}
    print(json.dumps(counters), flush=True)
    ok = all(r["equal"] for r in results.values())
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
