"""Engine stage: training driver over the encode/compute/decode stages.

Algorithm 1, generalized three ways beyond the paper (DESIGN.md §6):

  * MULTI-CLASS — W is a (d, c) matrix of c one-vs-all logistic heads; the
    dataset is encoded once and every round's single worker pass serves all
    c heads (compute.py amortizes the X̃ read).
  * MINI-BATCH SGD — each round selects ``cfg.batch_rows`` rows of the
    once-encoded shares.  Row selection commutes with Lagrange encoding
    (encoding is elementwise-linear across the K parts), so a row-subset of
    X̃_i is a valid encoding of the same row-subset of every X̄_k: the paper's
    one-time-encoding property survives mini-batching.
  * FULLY-JITTED SCAN — train() runs ONE jitted jax.lax.scan over all
    iterations: per-round PRNG keys are pre-split, survivor patterns are a
    static schedule whose decode matrices are precomputed host-side and
    stacked, and batch indices are pre-drawn.  No host↔device round trip or
    re-trace per iteration.  ``train_reference`` is the per-step loop the
    scan must match bit-for-bit (tests/test_scan_engine.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize, sigmoid_poly
from repro.core.protocol import compute, decode, encode
from repro.core.protocol.config import CPMLConfig
from repro.obs.trace import phase

# device scopes of one round (jax.named_scope): they name the ops of the
# weight encode, the worker polynomial and the decode + gradient step in
# the compiled program's op_name metadata, shared by _round and the scan
SCOPE_ENCODE = "cpml_encode_weights"
SCOPE_WORKER = "cpml_worker"
SCOPE_DECODE = "cpml_decode"
# and the scope of each job's compiled dataset encode (engine.setup)
SCOPE_ENCODE_DATASET = encode.SCOPE_ENCODE_DATASET
# The scopes live only in op metadata, which JAX's persistent compile cache
# leaves out of its key by default: an executable cached by a build of the
# same program without them comes back with stale op_names in its profile.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# ---------------------------------------------------------------------------
# State + setup
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CPMLState:
    w: jax.Array            # real weights: (d,) when c == 1, else (d, c)
    x_shares: jax.Array     # (N, mk, d) coded dataset (encoded ONCE)
    xty: jax.Array          # real X̄ᵀY, full padded data: (d,) or (d, c)
    m: int                  # number of (unpadded) samples
    mk: int                 # rows per part (padded m / K)
    xq_real: jax.Array      # dequantized dataset (m_padded, d) — loss/oracle
    xq_parts: jax.Array | None  # the same, split (K, mk, d) — mini-batch
    y: jax.Array            # padded labels, original form (m_padded,)
    y_parts: jax.Array | None   # targets split (K, mk, c) real (one-hot if
    #                             c>1); both None for full-batch jobs


def _targets(cfg: CPMLConfig, y: jax.Array) -> jax.Array:
    """(m,) labels -> (m, c) real regression targets for the c heads."""
    if cfg.c == 1:
        return y.astype(jnp.float32)[:, None]
    return jax.nn.one_hot(y.astype(jnp.int32), cfg.c, dtype=jnp.float32)


def setup(cfg: CPMLConfig, key: jax.Array, x: jax.Array, y: jax.Array,
          w0: jax.Array | None = None, dataset_encoder=None) -> CPMLState:
    """Encode the dataset + precompute all master-side cleartext context.

    y: (m,) float 0/1 labels when cfg.c == 1, integer class ids otherwise.
    ``dataset_encoder`` (same signature as encode.encode_dataset) lets a
    sharded master group own the encode (cluster/master_group.py) — it must
    be bit-identical to the default, which the group guarantees by drawing
    all randomness at full shape.

    The master's cleartext is held once: ``xq_real``, and its split
    ``xq_parts`` / ``y_parts`` only where mini-batch rounds read them.
    """
    kx, _ = jax.random.split(key)
    encoder = dataset_encoder or encode.encode_dataset
    with phase("setup.encode_dataset"):
        x_shares, ctx = encoder(cfg, kx, x)
    xq_real = _dequantize(ctx.pop("xq"), cfg.lx, cfg.p)
    if compute.shard_devices(cfg) is not None:
        # shares placed by worker are sized to fill the chips: the
        # quantized copy goes before the step size's transpose is made,
        # where otherwise the host would dispatch it while both are live
        jax.block_until_ready(xq_real)
    m_padded = ctx["m_padded"]
    mk = m_padded // cfg.K
    y_pad = jnp.concatenate([y, jnp.zeros(m_padded - y.shape[0], y.dtype)])
    targets = _targets(cfg, y_pad)                       # (m_padded, c)
    xty = _w_public(cfg, _xty(xq_real, targets))         # (d,) or (d, c)
    d = x.shape[1]
    if w0 is None:
        w = jnp.zeros((d,) if cfg.c == 1 else (d, cfg.c), jnp.float32)
    else:
        w = w0
    parts = cfg.batch_rows is not None
    return CPMLState(
        w=w, x_shares=x_shares, xty=xty, m=x.shape[0], mk=mk,
        xq_real=xq_real,
        xq_parts=xq_real.reshape(cfg.K, mk, d) if parts else None,
        y=y_pad, y_parts=targets.reshape(cfg.K, mk, cfg.c) if parts else None)


# one program, so the dequantized dataset is the only full-size buffer it
# makes (elementwise and exact: the same values as the eager ops)
_dequantize = jax.jit(quantize.dequantize, static_argnums=(1, 2))


# Xᵀy as one program: the transpose folds into the product, so no
# transposed copy of the dataset is made.  Its terms are multiples of
# 2^-lx times 0/1 targets, summed exactly in float32 in any order: the same
# values as the eager transpose and product.
_xty = jax.jit(lambda xq_real, targets: xq_real.T @ targets)


def _w_internal(cfg: CPMLConfig, w: jax.Array) -> jax.Array:
    return w[:, None] if cfg.c == 1 and w.ndim == 1 else w


def _w_public(cfg: CPMLConfig, w2: jax.Array) -> jax.Array:
    return w2[:, 0] if cfg.c == 1 else w2


# ---------------------------------------------------------------------------
# One protocol round (shared verbatim by step(), train_reference(), and the
# scan body — this sharing is what makes scan-vs-loop bit-identity hold)
# ---------------------------------------------------------------------------

def _gradient_step(cfg: CPMLConfig, w2: jax.Array, xg: jax.Array,
                   xq_parts: jax.Array, y_parts: jax.Array,
                   xty_full: jax.Array, batch_idx: jax.Array | None,
                   eta: jax.Array, m_int: jax.Array) -> jax.Array:
    """Apply one gradient step given the decoded real gradient xg (d, c).

    Batch index i selects global sample k*mk + i from every part k; rows
    with k*mk + i >= m are all-zero padding, so the 1/batch normalization
    counts only the real rows — otherwise rounds touching the padded tail
    would take a systematically smaller step.
    """
    if batch_idx is None:
        xty = xty_full
        scale = eta / m_int.astype(jnp.float32)
    else:
        xqb = jnp.take(xq_parts, batch_idx, axis=1)      # (K, b, d)
        yb = jnp.take(y_parts, batch_idx, axis=1)        # (K, b, c)
        xty = jnp.einsum("kbd,kbc->dc", xqb, yb)
        mk = xq_parts.shape[1]
        part0 = jnp.arange(cfg.K, dtype=jnp.int32) * mk  # global row offsets
        real = jnp.sum((batch_idx[None, :] + part0[:, None]) < m_int)
        scale = eta / real.astype(jnp.float32)
    return w2 - scale * (xg - xty)


def _round_update(cfg: CPMLConfig, w2: jax.Array, fastest: jax.Array,
                  xq_parts: jax.Array, y_parts: jax.Array,
                  xty_full: jax.Array, dmat: jax.Array,
                  batch_idx: jax.Array | None, eta: jax.Array,
                  m_int: jax.Array) -> jax.Array:
    """Decode the survivors' results and apply the gradient step.

    fastest: (R, d, c) field evaluations in responder order — either sliced
    out of a master-side all_worker_results (the simulated paths, _round) or
    received over the wire from real worker processes (runner socket mode).
    Both paths flow through THIS function, so where the worker compute ran
    cannot change what the update computes.
    """
    with jax.named_scope(SCOPE_DECODE):
        xg = decode.decode_gradient(cfg, fastest, dmat)            # (d, c)
        return _gradient_step(cfg, w2, xg, xq_parts, y_parts, xty_full,
                              batch_idx, eta, m_int)


def _update_from_parts(cfg: CPMLConfig, w2: jax.Array, parts: jax.Array,
                       xq_parts: jax.Array, y_parts: jax.Array,
                       xty_full: jax.Array, batch_idx: jax.Array | None,
                       eta: jax.Array, m_int: jax.Array) -> jax.Array:
    """Gradient step from ALREADY-DECODED (K, d, c) field parts.

    The streaming-decode path (decode.StreamingDecoder folds shares on the
    host as they arrive) lands here: the parts are exact integers identical
    to decode_parts' output, and parts_to_gradient + _gradient_step are the
    same ops _round_update composes — so a streamed round stays
    bit-identical to the batch-decoded one (tests/test_pipeline.py).
    """
    xg = decode.parts_to_gradient(cfg, parts)
    return _gradient_step(cfg, w2, xg, xq_parts, y_parts, xty_full,
                          batch_idx, eta, m_int)


def _round_body(cfg: CPMLConfig, w_shares: jax.Array, w2: jax.Array,
                x_shares: jax.Array, xq_parts: jax.Array, y_parts: jax.Array,
                xty_full: jax.Array, dmat: jax.Array, order: jax.Array,
                batch_idx: jax.Array | None, eta: jax.Array,
                m_int: jax.Array) -> jax.Array:
    """compute -> decode -> step, given this round's encoded weight shares
    (shared verbatim by the one-key and split-encode round variants)."""
    cbar = jnp.asarray(poly_coeffs(cfg), jnp.int32)
    xb = (x_shares if batch_idx is None
          else jnp.take(x_shares, batch_idx, axis=1))    # (N, b, d): the
    # coded sub-batch is the SAME row subset of every share / part.
    with jax.named_scope(SCOPE_WORKER):
        results = compute.all_worker_results(cfg, cbar, xb, w_shares)
    fastest = jnp.take(results, order, axis=0)                     # (R, d, c)
    return _round_update(cfg, w2, fastest, xq_parts, y_parts, xty_full,
                         dmat, batch_idx, eta, m_int)


def _round(cfg: CPMLConfig, key: jax.Array, w2: jax.Array,
           x_shares: jax.Array, xq_parts: jax.Array, y_parts: jax.Array,
           xty_full: jax.Array, dmat: jax.Array, order: jax.Array,
           batch_idx: jax.Array | None, eta: jax.Array, m_int: jax.Array
           ) -> jax.Array:
    """w2 (d, c) -> updated (d, c).  One full encode->compute->decode round
    with the N workers enacted on-device (vmap/shard, DESIGN.md §4)."""
    with jax.named_scope(SCOPE_ENCODE):
        w_shares = encode.encode_weights(cfg, key, w2)   # (N, d, c, r)
    return _round_body(cfg, w_shares, w2, x_shares, xq_parts, y_parts,
                       xty_full, dmat, order, batch_idx, eta, m_int)


def _round_split(cfg: CPMLConfig, kq: jax.Array, mask_shares: jax.Array,
                 w2: jax.Array, x_shares: jax.Array, xq_parts: jax.Array,
                 y_parts: jax.Array, xty_full: jax.Array, dmat: jax.Array,
                 order: jax.Array, batch_idx: jax.Array | None,
                 eta: jax.Array, m_int: jax.Array) -> jax.Array:
    """_round with the W-independent encode half supplied from outside.

    (kq, mask_shares) come from ``round_mask_context`` — typically built by
    the pipeline prefetcher while the PREVIOUS round was in flight.  The
    encode split is exact, so this is bit-identical to _round on the same
    round key (tests/test_pipeline.py)."""
    with jax.named_scope(SCOPE_ENCODE):
        w_shares = encode.encode_weights_finish(cfg, kq, mask_shares, w2)
    return _round_body(cfg, w_shares, w2, x_shares, xq_parts, y_parts,
                       xty_full, dmat, order, batch_idx, eta, m_int)


_round_jit = jax.jit(_round, static_argnums=(0,))
_round_split_jit = jax.jit(_round_split, static_argnums=(0,))
_round_update_jit = jax.jit(_round_update, static_argnums=(0,))
_update_from_parts_jit = jax.jit(_update_from_parts, static_argnums=(0,))
_encode_weights_jit = jax.jit(encode.encode_weights, static_argnums=(0,))
_weight_mask_jit = jax.jit(encode.weight_mask_shares, static_argnums=(0, 2))
_encode_finish_jit = jax.jit(encode.encode_weights_finish,
                             static_argnums=(0,))


def _scale_args(cfg: CPMLConfig, eta: float, state: CPMLState):
    """(eta, m) scalars for _round's gradient normalization."""
    return (jnp.float32(eta), jnp.int32(state.m))


def round_fn(cfg: CPMLConfig, state: CPMLState, eta: float
             ) -> Callable[..., jax.Array]:
    """Per-round hook: the EXACT round train()/train_reference() run.

    Returns ``run(key, w2, dmat, order, batch_idx=None) -> w2`` closing over
    the once-encoded dataset state.  External drivers (cluster/runner.py)
    that discover survivor patterns online call this with their observed
    decode matrix + responder order and stay bit-identical to the static
    schedule drivers replaying the same trace.
    """
    scale = _scale_args(cfg, eta, state)
    xty2 = _w_internal(cfg, state.xty)

    def run(key: jax.Array, w2: jax.Array, dmat: jax.Array, order: jax.Array,
            batch_idx: jax.Array | None = None) -> jax.Array:
        return _round_jit(cfg, key, w2, state.x_shares, state.xq_parts,
                          state.y_parts, xty2, dmat, order, batch_idx, *scale)

    return run


def round_fn_split(cfg: CPMLConfig, state: CPMLState, eta: float
                   ) -> Callable[..., jax.Array]:
    """round_fn with the W-independent encode half supplied by the caller.

    Returns ``run(kq, mask_shares, w2, dmat, order, batch_idx=None) -> w2``
    — the pipelined in-process round: (kq, mask_shares) come from
    ``round_mask_context`` built ahead of time, and the result is
    bit-identical to round_fn on the same round key.
    """
    scale = _scale_args(cfg, eta, state)
    xty2 = _w_internal(cfg, state.xty)

    def run(kq: jax.Array, mask_shares: jax.Array, w2: jax.Array,
            dmat: jax.Array, order: jax.Array,
            batch_idx: jax.Array | None = None) -> jax.Array:
        return _round_split_jit(cfg, kq, jnp.asarray(mask_shares), w2,
                                state.x_shares, state.xq_parts,
                                state.y_parts, xty2, dmat, order,
                                batch_idx, *scale)

    return run


def update_from_parts_fn(cfg: CPMLConfig, state: CPMLState, eta: float
                         ) -> Callable[..., jax.Array]:
    """Decode-and-update hook for STREAMED rounds (DESIGN.md §9).

    Returns ``run(w2, parts, batch_idx=None) -> w2`` where ``parts`` is the
    (K, d, c) field output of ``decode.StreamingDecoder.finish`` — the
    already-decoded sub-gradients.  parts_to_gradient + the shared
    _gradient_step make it bit-identical to update_fn on the equivalent
    (fastest, dmat) inputs.
    """
    scale = _scale_args(cfg, eta, state)
    xty2 = _w_internal(cfg, state.xty)

    def run(w2: jax.Array, parts: jax.Array,
            batch_idx: jax.Array | None = None) -> jax.Array:
        return _update_from_parts_jit(cfg, w2, jnp.asarray(parts, jnp.int32),
                                      state.xq_parts, state.y_parts, xty2,
                                      batch_idx, *scale)

    return run


def update_fn(cfg: CPMLConfig, state: CPMLState, eta: float
              ) -> Callable[..., jax.Array]:
    """Decode-and-update hook for drivers whose worker compute ran ELSEWHERE.

    Returns ``run(w2, fastest, dmat, batch_idx=None) -> w2`` where
    ``fastest`` is the (R, d, c) field results of the first ``threshold``
    responders in arrival order — e.g. deserialized from real worker
    processes over a socket transport.  It is the same ``_round_update``
    the in-process round composes, so a distributed round that feeds back
    bit-faithful worker results produces bit-identical weights.
    """
    scale = _scale_args(cfg, eta, state)
    xty2 = _w_internal(cfg, state.xty)

    def run(w2: jax.Array, fastest: jax.Array, dmat: jax.Array,
            batch_idx: jax.Array | None = None) -> jax.Array:
        return _round_update_jit(cfg, w2, fastest, state.xq_parts,
                                 state.y_parts, xty2, dmat, batch_idx, *scale)

    return run


def encode_round_shares(cfg: CPMLConfig, key: jax.Array, w2: jax.Array
                        ) -> jax.Array:
    """Round-t weight shares (N, d, c, r) for external dispatch.

    Same ``encode.encode_weights`` call ``_round`` traces with the same key
    — field elements are exact int32, so shares shipped to worker processes
    are bit-identical to the ones the in-process round would have used.
    """
    return _encode_weights_jit(cfg, key, w2)


def round_mask_context(cfg: CPMLConfig, key: jax.Array,
                       w_shape: tuple[int, ...]
                       ) -> tuple[jax.Array, jax.Array]:
    """W-INDEPENDENT half of round t's weight encode (DESIGN.md §9).

    Everything ``encode_round_shares(cfg, round_key(kloop, t), w2)`` does
    that does not need w2: the key split, the T fresh privacy masks, and
    their encoded contribution.  Returns ``(kq, mask_shares)``; feed them to
    ``encode_round_shares_split`` once the previous round's weights decode.
    Because it only needs (kloop, t, shape), a pipelined master computes it
    while round t-1 is still in flight.
    """
    return _weight_mask_jit(cfg, key, tuple(int(s) for s in w_shape))


def encode_round_shares_split(cfg: CPMLConfig, kq: jax.Array,
                              mask_shares: jax.Array, w2: jax.Array
                              ) -> jax.Array:
    """W-DEPENDENT half: bit-identical to ``encode_round_shares`` when
    (kq, mask_shares) came from ``round_mask_context`` on the same key."""
    return _encode_finish_jit(cfg, kq, jnp.asarray(mask_shares), w2)


def poly_coeffs(cfg: CPMLConfig) -> np.ndarray:
    """The quantized sigmoid-surrogate coefficients c̄ workers evaluate
    (one host-side derivation, shared by _round and worker provisioning)."""
    return np.asarray(
        sigmoid_poly.quantized_coeffs(cfg.r, cfg.lx, cfg.lw, cfg.lc, cfg.p),
        dtype=np.int32)


def step(cfg: CPMLConfig, key: jax.Array, state: CPMLState, eta: float,
         survivors: np.ndarray | None = None,
         batch_idx: jax.Array | None = None) -> CPMLState:
    """One master iteration.  survivors: indices of workers that responded
    (None = all N; only the fastest `threshold` are used, like the paper).
    batch_idx: (batch_rows,) row indices for this round's coded sub-batch
    (required iff cfg.batch_rows is set)."""
    surv = np.arange(cfg.N) if survivors is None else np.asarray(survivors)
    assert len(surv) >= cfg.threshold, "not enough survivors to decode"
    surv = surv[: cfg.threshold]
    dmat = decode.make_decode_matrix(cfg, surv)
    order = jnp.asarray(surv, jnp.int32)
    assert (batch_idx is not None) == (cfg.batch_rows is not None), \
        "batch_idx must be given exactly when cfg.batch_rows is set"
    w2 = _round_jit(cfg, key, _w_internal(cfg, state.w), state.x_shares,
                    state.xq_parts, state.y_parts, _w_internal(cfg, state.xty),
                    dmat, order, batch_idx, *_scale_args(cfg, eta, state))
    return dataclasses.replace(state, w=_w_public(cfg, w2))


# ---------------------------------------------------------------------------
# Static per-round schedule (keys / survivor decode matrices / batches)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Schedule:
    """Everything the scan needs per round, precomputed and stacked."""
    keys: jax.Array               # (iters, key) per-round weight-encode keys
    decode_mats: jax.Array        # (iters, R, K) int32 — survivor decode
    orders: jax.Array             # (iters, R) int32 — survivor indices
    batch_idx: jax.Array | None   # (iters, b) int32 or None (full batch)


def round_key(kloop: jax.Array, t: int) -> jax.Array:
    """Round t's weight-encode key — one derivation shared by the static
    schedule (make_schedule) and online drivers (cluster/runner.py)."""
    return jax.random.fold_in(kloop, t)


def draw_batch(cfg: CPMLConfig, kloop: jax.Array, iters: int, mk: int,
               t: int) -> jax.Array:
    """Round t's coded sub-batch indices (batch_rows,) int32.

    Keyed at ``iters + t`` so batch draws never collide with round_key's
    ``t`` stream.  Shared by make_schedule and online drivers so replaying
    a responder trace reproduces the identical batches bit-for-bit.
    """
    assert cfg.batch_rows is not None
    assert cfg.batch_rows <= mk, (
        f"batch_rows={cfg.batch_rows} exceeds the {mk} rows per "
        f"encoded part (padded m / K)")
    bkey = jax.random.fold_in(kloop, iters + t)
    return jax.random.choice(bkey, mk, (cfg.batch_rows,),
                             replace=False).astype(jnp.int32)


def survivor_round(cfg: CPMLConfig, surv: np.ndarray | None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Survivor indices -> (decode matrix (R, K), order (R,)) for one round."""
    surv = np.arange(cfg.N) if surv is None else np.asarray(surv)
    assert len(surv) >= cfg.threshold, (
        f"{len(surv)} survivors < recovery threshold {cfg.threshold}")
    surv = surv[: cfg.threshold]
    return (np.asarray(decode.make_decode_matrix(cfg, surv)),
            surv.astype(np.int32))


def make_schedule(cfg: CPMLConfig, kloop: jax.Array, iters: int, mk: int,
                  survivor_fn: Callable[[int], np.ndarray] | None = None
                  ) -> Schedule:
    keys = jax.vmap(lambda t: round_key(kloop, t))(jnp.arange(iters))
    dmats, orders = [], []
    for t in range(iters):
        surv = survivor_fn(t) if survivor_fn is not None else None
        try:
            dmat, order = survivor_round(cfg, surv)
        except AssertionError as e:
            raise AssertionError(f"round {t}: {e}") from None
        dmats.append(dmat)
        orders.append(order)
    batch_idx = None
    if cfg.batch_rows is not None:
        batch_idx = jnp.stack([draw_batch(cfg, kloop, iters, mk, t)
                               for t in range(iters)])
    return Schedule(keys=keys,
                    decode_mats=jnp.asarray(np.stack(dmats), jnp.int32),
                    orders=jnp.asarray(np.stack(orders), jnp.int32),
                    batch_idx=batch_idx)


# ---------------------------------------------------------------------------
# Training drivers: one jitted scan (production) + per-step reference loop
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _train_scan(cfg: CPMLConfig, eval_every: int, w0: jax.Array,
                x_shares: jax.Array, xq_parts: jax.Array, y_parts: jax.Array,
                xty_full: jax.Array, keys: jax.Array, dmats: jax.Array,
                orders: jax.Array, batch_idx: jax.Array | None,
                eta: jax.Array, m_int: jax.Array,
                x_eval: jax.Array | None, y_eval: jax.Array | None):
    def body(w2, xs):
        t, key, dmat, order, bidx = xs
        w_new = _round(cfg, key, w2, x_shares, xq_parts, y_parts, xty_full,
                       dmat, order, bidx, eta, m_int)
        if eval_every:
            # full-data metrics only on the rounds train() will report
            metrics = jax.lax.cond(
                (t + 1) % eval_every == 0,
                lambda w: _eval_metrics(cfg, w, x_eval, y_eval),
                lambda w: (jnp.float32(0), jnp.float32(0)),
                w_new)
            return w_new, metrics
        return w_new, None

    ts = jnp.arange(keys.shape[0])
    return jax.lax.scan(body, w0, (ts, keys, dmats, orders, batch_idx))


def train(cfg: CPMLConfig, key: jax.Array, x: jax.Array, y: jax.Array,
          iters: int, eta: float | None = None,
          survivor_fn: Callable[[int], np.ndarray] | None = None,
          eval_every: int = 0) -> tuple[jax.Array, list[dict[str, float]]]:
    """Full Algorithm 1 as ONE jitted scan.  Returns (w, history)."""
    with phase("train"):
        ksetup, kloop = jax.random.split(key)
        state = setup(cfg, ksetup, x, y)
        if eta is None:
            with phase("setup.step_size"):
                eta = lipschitz_eta(state.xq_real)
        with phase("setup.schedule"):
            sched = make_schedule(cfg, kloop, iters, state.mk, survivor_fn)
        # the evaluation's copy of the real rows only where it is read
        evals = ((state.xq_real[: state.m], state.y[: state.m])
                 if eval_every else (None, None))
        w2, metrics = _train_scan(
            cfg, int(eval_every), _w_internal(cfg, state.w), state.x_shares,
            state.xq_parts, state.y_parts, _w_internal(cfg, state.xty),
            sched.keys, sched.decode_mats, sched.orders, sched.batch_idx,
            *_scale_args(cfg, eta, state), *evals)
        history: list[dict[str, float]] = []
        if eval_every:
            losses, accs = metrics
            for t in range(eval_every - 1, iters, eval_every):
                history.append({"iter": t + 1, "loss": float(losses[t]),
                                "acc": float(accs[t])})
        return _w_public(cfg, w2), history


def train_reference(cfg: CPMLConfig, key: jax.Array, x: jax.Array,
                    y: jax.Array, iters: int, eta: float | None = None,
                    survivor_fn: Callable[[int], np.ndarray] | None = None,
                    eval_every: int = 0
                    ) -> tuple[jax.Array, list[dict[str, float]]]:
    """Per-step loop over the SAME schedule/round function as train().

    Exists as the bit-exactness oracle for the scan engine (and as the
    debuggable path: each round is a separate jit call you can inspect).
    """
    ksetup, kloop = jax.random.split(key)
    state = setup(cfg, ksetup, x, y)
    if eta is None:
        eta = lipschitz_eta(state.xq_real)
    sched = make_schedule(cfg, kloop, iters, state.mk, survivor_fn)
    run = round_fn(cfg, state, eta)
    w2 = _w_internal(cfg, state.w)
    history: list[dict[str, float]] = []
    for t in range(iters):
        bidx = None if sched.batch_idx is None else sched.batch_idx[t]
        w2 = run(sched.keys[t], w2, sched.decode_mats[t], sched.orders[t],
                 bidx)
        if eval_every and (t + 1) % eval_every == 0:
            l, a = _eval_metrics(cfg, w2, state.xq_real[: state.m],
                                 state.y[: state.m])
            history.append({"iter": t + 1, "loss": float(l), "acc": float(a)})
    return _w_public(cfg, w2), history


# ---------------------------------------------------------------------------
# Cleartext-side helpers: step size, metrics
# ---------------------------------------------------------------------------

def lipschitz_eta(xq_real: jax.Array) -> float:
    """eta = 1/L.  The cost (Eq. 1) carries a 1/m, so its Hessian is
    (1/m) X̄ᵀ S X̄ with S ⪯ I/4, giving L = max eig(X̄ᵀX̄)/(4m).
    (The paper's Lemma 2 states L = ||X̄||₂²/4, omitting the 1/m that its own
    Eq. (1) introduces — with that L the step size is m× too small to
    reproduce Fig. 3's 25-iteration accuracy.)  One-vs-all heads share the
    same X, hence the same L."""
    # power iteration — avoids O(d^3) eigendecomposition for large d.
    m, d = xq_real.shape
    # Xᵀ made once: an eager ``xq_real.T`` in the loop copies the dataset
    # every step, and the steps are dispatched ahead of the device, so
    # several copies can be live at once.  The products are the same
    # programs on the same values.
    xt = xq_real.T
    v = jnp.ones((d,), jnp.float32) / np.sqrt(d)
    for _ in range(50):
        v = xt @ (xq_real @ v)
        v = v / (jnp.linalg.norm(v) + 1e-30)
    lam = v @ (xt @ (xq_real @ v))
    return float(4.0 * m / lam)


def sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def cleartext_baseline(cfg: CPMLConfig, x: jax.Array, y: jax.Array,
                       iters: int, eta: float | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Non-private GD on the quantized dataset with the TRUE sigmoid.

    The comparison baseline the paper's Fig. 3/4 plots against: same X̄ as
    the coded engine sees, no polynomial surrogate, no coding.  Returns
    (w, xq) with w shaped like train()'s output ((d,) when c == 1) and xq
    the dequantized dataset for metric evaluation.
    """
    xq = quantize.dequantize(quantize.quantize_data(x, cfg.lx, cfg.p),
                             cfg.lx, cfg.p)
    m = x.shape[0]
    if eta is None:
        eta = lipschitz_eta(xq)
    targets = _targets(cfg, y)                           # (m, c)
    w = jnp.zeros((x.shape[1], cfg.c))
    for _ in range(iters):
        w = w - eta * (xq.T @ (sigmoid(xq @ w) - targets)) / m
    return _w_public(cfg, w), xq


def loss_and_accuracy(w: jax.Array, x: jax.Array, y: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Binary logistic loss + accuracy (w (d,), y (m,) in {0,1})."""
    z = x @ w
    yhat = sigmoid(z)
    eps = 1e-7
    loss = -jnp.mean(y * jnp.log(yhat + eps) + (1 - y) * jnp.log(1 - yhat + eps))
    acc = jnp.mean((yhat > 0.5) == (y > 0.5))
    return loss, acc


def multiclass_loss_and_accuracy(w: jax.Array, x: jax.Array, labels: jax.Array
                                 ) -> tuple[jax.Array, jax.Array]:
    """One-vs-all logistic loss (mean over heads) + argmax accuracy.

    w (d, c), labels (m,) integer class ids.
    """
    z = x @ w                                            # (m, c)
    yhat = sigmoid(z)
    onehot = jax.nn.one_hot(labels.astype(jnp.int32), w.shape[1],
                            dtype=jnp.float32)
    eps = 1e-7
    loss = -jnp.mean(onehot * jnp.log(yhat + eps)
                     + (1 - onehot) * jnp.log(1 - yhat + eps))
    acc = jnp.mean(jnp.argmax(z, axis=1) == labels.astype(jnp.int32))
    return loss, acc


def per_class_accuracy(w: jax.Array, x: jax.Array, labels: jax.Array
                       ) -> jax.Array:
    """(c,) recall per class under the argmax decision rule."""
    pred = jnp.argmax(x @ w, axis=1)
    labels = labels.astype(jnp.int32)
    c = w.shape[1]
    hit = jnp.zeros((c,)).at[labels].add(pred == labels)
    cnt = jnp.zeros((c,)).at[labels].add(1.0)
    return hit / jnp.maximum(cnt, 1.0)


def _eval_metrics(cfg: CPMLConfig, w2: jax.Array, x: jax.Array,
                  y: jax.Array) -> tuple[jax.Array, jax.Array]:
    if cfg.c == 1:
        return loss_and_accuracy(w2[:, 0], x, y)
    return multiclass_loss_and_accuracy(w2, x, y)
