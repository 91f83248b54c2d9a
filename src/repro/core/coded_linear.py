"""Beyond-paper: Lagrange-coded tensor-parallel linear layer.

The paper codes the *training data* for privacy + stragglers.  The same
machinery applies to a pure-matmul INFERENCE layer (the LM head): partition
the weight matrix W (d, v) into K column blocks, add T random mask blocks,
Lagrange-encode into N shares W̃_i — one per TP device.  Every device computes
Y_i = H @ W̃_i; since f is degree-1 in W̃, ANY K+T of the N results reconstruct
all K true column blocks (recovery threshold K+T, Theorem 1 with 'deg f'=1).

What this buys on a 1000+-node cluster:
  * straggler/failure tolerance for TP: N-(K+T) device losses survivable per
    coded group without recomputation;
  * T-collusion privacy of the *model weights* against compromised hosts
    (and of activations, in the dual activation-coded mode).
Cost: N/K compute overhead and quantization of H/W (lh/lw fixed-point bits).

This is `--coded-head` in launch/serve.py; tests/test_coded_linear.py checks
exactness of the field path and the end-to-end fp error bound.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import field, lagrange, quantize


@dataclasses.dataclass(frozen=True)
class CodedLinearConfig:
    N: int              # TP shards (devices in the coded group)
    K: int              # data blocks (useful fraction = K/N)
    T: int              # privacy threshold
    lh: int = 6         # activation quantization bits (scale 2^lh)
    lw: int = 6         # weight quantization bits
    p: int = field.P30  # 30-bit prime: more headroom for d-long dot products

    def __post_init__(self):
        assert self.N >= self.K + self.T, "need N >= K+T (deg-1 threshold)"

    @property
    def threshold(self) -> int:
        return lagrange.degree_threshold(self.K, self.T, deg_f=1)

    @property
    def scheme(self) -> lagrange.CodingScheme:
        return lagrange.CodingScheme(self.N, self.K, self.T, self.p)


def encode_weights(cfg: CodedLinearConfig, key: jax.Array, w: jax.Array
                   ) -> jax.Array:
    """w: (d, v) real -> coded shares (N, d, v/K) in F_p.  Done once."""
    d, v = w.shape
    assert v % cfg.K == 0, f"vocab {v} must divide into K={cfg.K} blocks"
    wq = quantize.quantize_data(w, cfg.lw, cfg.p)
    parts = wq.reshape(d, cfg.K, v // cfg.K).transpose(1, 0, 2)  # (K, d, v/K)
    masks = lagrange.draw_masks(key, cfg.T, parts.shape[1:], cfg.p)
    return lagrange.encode(cfg.scheme, parts, masks, cfg.p)


def worker_matmul(cfg: CodedLinearConfig, h_q: jax.Array, w_share: jax.Array
                  ) -> jax.Array:
    """One shard's compute: H̄ @ W̃_i over F_p.  (m, d) x (d, v/K)."""
    return field.matmul(h_q, w_share, cfg.p)


def decode_output(cfg: CodedLinearConfig, results: jax.Array,
                  survivors: np.ndarray) -> jax.Array:
    """(S, m, v/K) survivor results -> (m, v) real logits."""
    dec = lagrange.decode(cfg.scheme, results, survivors, deg_f=1, p=cfg.p)
    out = quantize.dequantize(dec, cfg.lh + cfg.lw, cfg.p)  # (K, m, v/K)
    return out.transpose(1, 0, 2).reshape(results.shape[1], -1)


def coded_head_apply(cfg: CodedLinearConfig, h: jax.Array,
                     w_shares: jax.Array,
                     survivors: np.ndarray | None = None) -> jax.Array:
    """Full coded projection: h (m, d) real -> logits (m, v) real.

    `survivors=None` uses the first K+T shards (no failures); pass any index
    set of size >= K+T to simulate stragglers/failures.
    """
    surv = np.arange(cfg.N) if survivors is None else np.asarray(survivors)
    h_q = quantize.quantize_data(h, cfg.lh, cfg.p)
    results = jax.vmap(lambda ws: worker_matmul(cfg, h_q, ws))(
        w_shares[jnp.asarray(surv[: cfg.threshold])])
    return decode_output(cfg, results, surv[: cfg.threshold])


def coded_head_apply_sharded(cfg: CodedLinearConfig, mesh, axis: str,
                             h: jax.Array, w_shares: jax.Array,
                             survivors: tuple[int, ...] | None = None
                             ) -> jax.Array:
    """shard_map version: one share per device along `axis` (size N).

    `survivors` is a STATIC index tuple (the runtime's heartbeat monitor
    picks it; each pattern compiles once — patterns change at node-failure
    frequency, i.e. rarely).  Every device computes its share's matmul with
    zero collectives; one all_gather plays "send to master"; the decode is a
    replicated (threshold x K) field matmul.  Used by launch/serve.py
    --coded-head and the coded-head dry-run cell.
    """
    from jax.sharding import PartitionSpec as Pspec
    surv = np.arange(cfg.N) if survivors is None else np.asarray(survivors)
    h_q = quantize.quantize_data(h, cfg.lh, cfg.p)

    def body(ws):
        res = worker_matmul(cfg, h_q, ws[0])[None]          # (1, m, v/K)
        return jax.lax.all_gather(res, axis, axis=0, tiled=True)  # (N, m, v/K)

    results = jax.shard_map(body, mesh=mesh, in_specs=(Pspec(axis),),
                            out_specs=Pspec())(w_shares)
    picked = jnp.take(results, jnp.asarray(surv[: cfg.threshold]), axis=0)
    return decode_output(cfg, picked, surv[: cfg.threshold])
