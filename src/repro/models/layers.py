"""Transformer substrate layers (pure-JAX, sharding-annotation friendly).

Attention is blockwise ("flash-style" at the XLA level): a python loop over
query blocks with a lax.scan over only the STATICALLY-valid kv blocks per
query block (causal upper bound, sliding-window lower bound).  This keeps the
S x S logits tensor out of HBM — mandatory for the 32k cells — and also
removes the masked-out FLOPs from the compiled HLO (2x for causal, much more
for SWA), which shows up directly in the roofline compute term.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig


class ParamSpec(NamedTuple):
    """Template leaf: shape + logical axis names (sharding) + init scale."""
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"      # normal | zeros | ones


# ---------------------------------------------------------------------------
# primitive forwards
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def swiglu(x: jax.Array, w1, w3, w2) -> jax.Array:
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def gelu_mlp(x: jax.Array, w1, w2) -> jax.Array:
    return jax.nn.gelu(x @ w1) @ w2


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise attention (training / prefill)
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal: bool, window: int | None):
    """(qb, kb) additive bias: 0 valid, -inf invalid."""
    valid = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        valid &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        valid &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        q_block: int = 512, kv_block: int = 1024,
                        softcap: float | None = None,
                        compute_dtype: str = "f32",
                        row_offset: jax.Array | None = None) -> jax.Array:
    """Online-softmax attention.  q: (B,S,H,D), k/v: (B,Sk,KH,D) -> (B,S,H,D).

    Per query block the kv range is STATIC: [window-lower-bound, causal-upper-
    bound), rounded to kv_block tiles, so masked tiles are never computed.
    compute_dtype="bf16" feeds the QK and PV matmuls bf16 inputs with fp32
    accumulation (flash-attention numerics) — halves score-tile HBM traffic.
    """
    in_dt = jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32
    B, S, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    q_block = min(q_block, S)
    kv_block = min(kv_block, Sk)
    nq = -(-S // q_block)
    nk_total = -(-Sk // kv_block)
    q = (q * (D ** -0.5)).astype(q.dtype)
    # pad to block multiples
    Sp, Skp = nq * q_block, nk_total * kv_block
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
    qg = q.reshape(B, Sp, KH, G, D)
    outs = []
    # query i has absolute position row_offset + i.  A traced row_offset
    # (context-parallel shards) forces full static kv ranges + masking;
    # a python-int offset lets the block ranges skip masked tiles entirely.
    traced_off = row_offset is not None
    offset = row_offset if traced_off else (Sk - S)
    for i in range(nq):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 1)
        q_pos = offset + i * q_block + jnp.arange(q_block)
        if traced_off:
            lo, hi = 0, nk_total
        else:
            # static kv tile range for this query block
            hi = min(nk_total, -(-(offset + (i + 1) * q_block) // kv_block)) \
                if causal else nk_total
            lo = 0
            if window is not None:
                lo = max(0, (offset + i * q_block - window + 1) // kv_block)
            hi = max(hi, lo + 1)

        def kv_step(carry, j, qi=qi, q_pos=q_pos):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * kv_block, kv_block, 1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * kv_block, kv_block, 1)
            k_pos = j * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(in_dt),
                           kj.astype(in_dt),
                           preferred_element_type=jnp.float32)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            bias = _mask_bias(q_pos, k_pos, causal, window)
            # also mask kv padding
            bias = jnp.where((k_pos < Sk)[None, :], bias, -jnp.inf)
            s = s + bias[None, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            # fully-masked tiles (SWA rows whose window misses this tile)
            # leave m_new = -inf; exp(-inf - -inf) = nan — zero them instead.
            dead = jnp.isneginf(m_new)
            p = jnp.where(dead[..., None], 0.0, jnp.exp(s - m_new[..., None]))
            corr = jnp.where(dead, 0.0, jnp.exp(m - m_new))
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(in_dt),
                            vj.astype(in_dt),
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        # derive carries from qi so they inherit device-varying types under
        # shard_map (context-parallel path) — fresh zeros would be
        # replicated-typed and fail the scan carry check.
        qt = qi.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (B,KH,G,qb,D)
        m0 = jnp.full_like(qt[..., 0], -jnp.inf)
        l0 = jnp.zeros_like(qt[..., 0])
        a0 = jnp.zeros_like(qt)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(lo, hi))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(out.transpose(0, 3, 1, 2, 4))       # (B, qb, KH, G, D)
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.reshape(B, S, H, D).astype(q.dtype)


def context_parallel_attention(mesh, q: jax.Array, k: jax.Array,
                               v: jax.Array, *, causal: bool = True,
                               window: int | None = None, q_block: int = 512,
                               kv_block: int = 1024,
                               softcap: float | None = None,
                               compute_dtype: str = "f32") -> jax.Array:
    """Sequence-sharded self-attention for head counts that don't divide the
    TP axis (arctic 56, hymba 25, qwen2-vl 28, whisper 6).

    Each 'model' shard owns S/tp query rows (perfect load balance regardless
    of head count) and all-gathers the small GQA k/v once per layer —
    replacing GSPMD's fallback of 16x-replicated attention or score-tensor
    all-reduces (EXPERIMENTS.md §Perf cell B).  shard_map + explicit
    collectives; causality handled with a traced per-shard row offset.
    """
    from jax.sharding import PartitionSpec as P
    B, S, H, D = q.shape
    tp = mesh.shape["model"]
    assert S % tp == 0, (S, tp)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_ok = batch_axes and B % int(np.prod([mesh.shape[a]
                                           for a in batch_axes])) == 0
    bspec = ((batch_axes if len(batch_axes) > 1 else batch_axes[0])
             if b_ok else None)
    spec = P(bspec, "model", None, None)
    qb = min(q_block, S // tp)

    def body(q_l, k_l, v_l):
        k_f = jax.lax.all_gather(k_l, "model", axis=1, tiled=True)
        v_f = jax.lax.all_gather(v_l, "model", axis=1, tiled=True)
        off = jax.lax.axis_index("model") * (S // tp)
        return blockwise_attention(
            q_l, k_f, v_f, causal=causal, window=window, q_block=qb,
            kv_block=kv_block, softcap=softcap, compute_dtype=compute_dtype,
            row_offset=off)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: int | None = None
                     ) -> jax.Array:
    """Single-position attention vs a cache.

    q: (B, 1, H, D); k/v_cache: (B, Smax, KH, D); cache_len: () int32 —
    number of valid cache positions INCLUDING the current token.
    """
    B, _, H, D = q.shape
    _, Smax, KH, _ = k_cache.shape
    G = H // KH
    qg = q.reshape(B, KH, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    k_pos = jnp.arange(Smax)
    valid = k_pos[None, :] < cache_len
    if window is not None:
        valid &= k_pos[None, :] > (cache_len - 1 - window)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (params + forward)
# ---------------------------------------------------------------------------

def attn_template(cfg: ModelConfig) -> dict[str, ParamSpec]:
    """QKV/O projections.  The flattened heads*head_dim dim carries a
    COUNT-qualified logical axis `heads[n]`: the sharding rules only put it
    on the model axis when the head COUNT divides the axis — sharding the
    flat dim of a non-divisible head count makes GSPMD reshard at the
    (B,S,H,D) reshape and all-reduce score tensors (observed: 16x redundant
    attention for arctic's 56 heads; EXPERIMENTS.md §Perf cell B)."""
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hq, hkv = f"heads[{h}]", f"heads[{kh}]"
    t = {
        "wq": ParamSpec((d, h * hd), ("embed", hq)),
        "wk": ParamSpec((d, kh * hd), ("embed", hkv)),
        "wv": ParamSpec((d, kh * hd), ("embed", hkv)),
        "wo": ParamSpec((h * hd, d), (hq, "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((h * hd,), (hq,), init="zeros")
        t["bk"] = ParamSpec((kh * hd,), (hkv,), init="zeros")
        t["bv"] = ParamSpec((kh * hd,), (hkv,), init="zeros")
    return t


def attn_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"] + (p.get("bq", 0))
    k = x @ p["wk"] + (p.get("bk", 0))
    v = x @ p["wv"] + (p.get("bv", 0))
    q = rope(q.reshape(B, S, h, hd), positions, cfg.rope_theta)
    k = rope(k.reshape(B, S, kh, hd), positions, cfg.rope_theta)
    return q, k, v.reshape(B, S, kh, hd)


def attn_forward(cfg: ModelConfig, rc: RunConfig, p: dict, x: jax.Array,
                 positions: jax.Array, *, causal: bool = True,
                 window: int | None = None) -> jax.Array:
    q, k, v = attn_qkv(cfg, p, x, positions)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              q_block=rc.q_block, kv_block=rc.kv_block,
                              softcap=cfg.attn_logit_softcap,
                              compute_dtype=rc.attn_dtype)
    B, S, _ = x.shape
    return out.reshape(B, S, -1) @ p["wo"]


def attn_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                cache_index: jax.Array, *, window: int | None = None
                ) -> tuple[jax.Array, dict]:
    """x: (B, 1, d); cache: {'k','v'} (B, Smax, KH, hd). Returns (out, cache)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), cache_index, jnp.int32)
    q, k, v = attn_qkv(cfg, p, x, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, 1)
    out = decode_attention(q, k_cache, v_cache, cache_index + 1, window=window)
    new_cache = {"k": k_cache, "v": v_cache}
    return out.reshape(B, 1, -1) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLP block
# ---------------------------------------------------------------------------

def mlp_template(cfg: ModelConfig, ff: int | None = None) -> dict[str, ParamSpec]:
    d = cfg.d_model
    ff = ff or cfg.d_ff
    if cfg.act == "silu":
        return {"w1": ParamSpec((d, ff), ("embed", "ffn")),
                "w3": ParamSpec((d, ff), ("embed", "ffn")),
                "w2": ParamSpec((ff, d), ("ffn", "embed"))}
    return {"w1": ParamSpec((d, ff), ("embed", "ffn")),
            "w2": ParamSpec((ff, d), ("ffn", "embed"))}


def mlp_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        return swiglu(x, p["w1"], p["w3"], p["w2"])
    return gelu_mlp(x, p["w1"], p["w2"])
