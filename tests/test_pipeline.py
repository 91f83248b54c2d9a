"""Pipelined round engine acceptance (DESIGN.md §9).

The load-bearing invariants:

  * ENCODE SPLIT — the W-independent half (key split + fresh masks + their
    encoded contribution) composed with the W-dependent half (quantize +
    data-row encode + addmod) is bit-identical to the one-shot
    encode_weights on the same round key, for every (K, T, r, c) shape.
  * STREAMING DECODE — folding shares into the Lagrange reconstruction as
    they arrive equals the batch decode at exactly the threshold for EVERY
    responder-subset prefix, on hit (any arrival order of the predicted
    subset) and on miss (fallback).
  * PIPELINE MODES — ClusterRunner under every ``--pipeline`` mode stays
    bit-identical to train_reference replaying the observed trace, and all
    modes produce identical weights/traces (order-independent latencies),
    including through a mid-run dead worker.
  * TIMING MODEL — the scheduler charges encode/decode components to the
    simulated clock separately and records them next to t_first_R.
"""
import itertools
import math
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.cluster import (
    ClusterRunner,
    DeadWorkerLatency,
    DeterministicLatency,
    EventScheduler,
    LognormalTailLatency,
    RoundContext,
    RoundPrefetcher,
    make_latency,
)
from repro.core import protocol
from repro.core.protocol import decode, encode, engine
from repro.data import synthetic

PIPELINE_MODES = ("off", "prefetch", "streaming", "full")


@pytest.fixture(scope="module")
def binary_data():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=240, d=20)


# ---------------------------------------------------------------------------
# Encode split: W-independent + W-dependent halves == one-shot encode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,K,T,r,c", [
    (8, 2, 1, 1, 1),     # the paper's shape
    (10, 2, 2, 1, 3),    # more masks + multi-class heads
    (8, 3, 0, 1, 2),     # T=0: the mask half contributes zeros
    (8, 1, 1, 2, 1),     # degree-2 surrogate (r quantization draws)
])
def test_encode_split_bit_identical(N, K, T, r, c):
    cfg = protocol.CPMLConfig(N=N, K=K, T=T, r=r, c=c)
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(jax.random.PRNGKey(1), (13, c))
    full = encode.encode_weights(cfg, key, w)
    kq, mask_shares = encode.weight_mask_shares(cfg, key, w.shape)
    split = encode.encode_weights_finish(cfg, kq, mask_shares, w)
    assert (np.asarray(full) == np.asarray(split)).all()


def test_round_mask_context_matches_encode_round_shares():
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=2)
    key = engine.round_key(jax.random.PRNGKey(7), 5)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (9, 2))
    a = engine.encode_round_shares(cfg, key, w2)
    kq, mask_shares = engine.round_mask_context(cfg, key, w2.shape)
    b = engine.encode_round_shares_split(cfg, kq, mask_shares, w2)
    assert (np.asarray(a) == np.asarray(b)).all()


def test_encode_mask_shares_are_w_independent():
    """The same key yields the same mask context regardless of when (or on
    which thread) it is computed — the property the prefetcher rests on."""
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    key = engine.round_key(jax.random.PRNGKey(0), 3)
    kq1, ms1 = engine.round_mask_context(cfg, key, (5, 1))
    out = {}

    def worker():
        out["ctx"] = engine.round_mask_context(cfg, key, (5, 1))

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    kq2, ms2 = out["ctx"]
    assert (np.asarray(kq1) == np.asarray(kq2)).all()
    assert (np.asarray(ms1) == np.asarray(ms2)).all()


# ---------------------------------------------------------------------------
# Streaming decode == batch decode for every responder-subset prefix
# ---------------------------------------------------------------------------

def test_streaming_equals_batch_for_every_subset_prefix():
    """REGRESSION (the satellite invariant): streaming decode at exactly
    the threshold equals the batch decode for EVERY responder-subset
    prefix — all P(5, 4) = 120 arrival orders, hit and miss paths."""
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)        # threshold 4
    R = cfg.threshold
    rng = np.random.default_rng(0)
    H = rng.integers(0, cfg.p, (cfg.N, 6, 2)).astype(np.int32)
    hits = 0
    for perm in itertools.permutations(range(cfg.N), R):
        order = np.asarray(perm)
        dmat = protocol.make_decode_matrix(cfg, order)
        batch = np.asarray(decode.decode_parts(
            cfg, jnp.asarray(H[order, :, :]), dmat))
        # hit path: prediction is the same SUBSET in a different order
        plan = decode.prefix_decode_plan(cfg, np.asarray(sorted(perm)))
        sd = decode.StreamingDecoder(cfg, plan)
        for w in order:
            sd.fold(w, H[w])
        assert (sd.finish(order) == batch).all()
        hits += sd.streamed
        # miss path: prediction names a different subset -> exact fallback
        other = np.asarray([w for w in range(cfg.N) if w != perm[0]])
        sd2 = decode.StreamingDecoder(cfg, decode.prefix_decode_plan(
            cfg, other))
        for w in order:
            sd2.fold(w, H[w])
        assert (sd2.finish(order) == batch).all() and not sd2.streamed
        # no-plan path
        sd3 = decode.StreamingDecoder(cfg, None)
        for w in order:
            sd3.fold(w, H[w])
        assert (sd3.finish(order) == batch).all() and not sd3.streamed
    assert hits == 120     # any arrival order of the predicted subset hits


def test_streaming_ignores_arrivals_beyond_threshold():
    """collect_all keeps folding arrivals past the threshold; the decoder
    must not let them corrupt the reconstruction."""
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)
    R = cfg.threshold
    rng = np.random.default_rng(1)
    H = rng.integers(0, cfg.p, (cfg.N, 4, 1)).astype(np.int32)
    arrivals = [3, 0, 4, 1, 2]                 # all five respond
    order = np.asarray(arrivals[:R])
    plan = decode.prefix_decode_plan(cfg, np.asarray(arrivals))
    sd = decode.StreamingDecoder(cfg, plan)
    for w in arrivals:
        sd.fold(w, H[w])
    batch = np.asarray(decode.decode_parts(
        cfg, jnp.asarray(H[order, :, :]),
        protocol.make_decode_matrix(cfg, order)))
    assert sd.streamed is False                # finish() not called yet
    assert (sd.finish(order) == batch).all() and sd.streamed


def test_prefix_plan_requires_full_threshold():
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)
    assert decode.prefix_decode_plan(cfg, None) is None
    assert decode.prefix_decode_plan(cfg, np.array([1, 2])) is None


# ---------------------------------------------------------------------------
# Scheduler: encode/decode components charged + recorded separately
# ---------------------------------------------------------------------------

def test_scheduler_charges_encode_decode_components():
    sched = EventScheduler(4, DeterministicLatency(base=1.0, skew=1.0))
    trace = sched.dispatch_round(0, threshold=2, pre_s=0.5, post_s=0.25)
    # encode charged BEFORE dispatch: t_start moved, the wait did not
    assert trace.t_start == pytest.approx(0.5)
    assert trace.t_first_R == pytest.approx(2.5)         # worker 1 at +2.0
    assert trace.coded_wait_s == pytest.approx(2.0)
    assert trace.encode_s == pytest.approx(0.5)
    assert trace.decode_s == pytest.approx(0.25)
    assert trace.critical_path_s == pytest.approx(0.5 + 2.0 + 0.25)
    # decode charged AFTER the decode instant, visible on the clock
    assert sched.clock == pytest.approx(2.75)
    assert trace.t_ready == pytest.approx(2.75)


def test_scheduler_on_result_fires_in_arrival_order():
    sched = EventScheduler(4, DeterministicLatency(base=1.0, skew=1.0))
    seen = []
    sched.dispatch_round(0, threshold=3,
                         on_result=lambda w, payload: seen.append(w))
    assert seen == [0, 1, 2]


def test_runner_wait_stats_expose_components(binary_data):
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                           DeterministicLatency(base=1.0, skew=0.1),
                           pipeline="full",
                           encode_cost_s=0.3, decode_cost_s=0.14)
    runner.run(6)
    stats = runner.wait_stats()
    # prefetch leaves the K/(K+T) data fraction of the encode ...
    assert stats["encode"]["mean"] == pytest.approx(0.3 * 2 / 3)
    # ... and streaming leaves one fold of threshold on a subset-prediction
    # hit, but the FULL decode cost on a miss (honest fallback accounting)
    hits = stats["rounds"]["streamed"]
    misses = 6 - hits
    assert stats["decode"]["mean"] == pytest.approx(
        (hits * 0.14 / cfg.threshold + misses * 0.14) / 6)
    assert stats["critical_path"]["mean"] == pytest.approx(
        stats["encode"]["mean"] + stats["coded_T"]["mean"]
        + stats["decode"]["mean"])
    assert stats["rounds"]["prefetched"] == 6.0
    # prefetched plans lag a round (built while the previous round is in
    # flight), so under a CONSTANT responder order everything from round 2
    # streams; rounds 0/1 depend on producer/consumer interleaving
    assert hits >= 4.0


# ---------------------------------------------------------------------------
# RoundPrefetcher: one-ahead production, rewind, clean close
# ---------------------------------------------------------------------------

def _ctx(t):
    return RoundContext(t=t, kq=None, mask_shares=np.zeros(1),
                        batch_idx=None, plan=None)


def test_prefetcher_serves_in_order_and_rewinds():
    built = []

    def build(t):
        built.append(t)
        return _ctx(t)

    with RoundPrefetcher(build, start=0, stop=10) as pf:
        assert pf.get(0).t == 0
        assert pf.get(1).t == 1
        # checkpoint-restore rewind: an unexpected t resets the producer
        assert pf.get(0).t == 0
        assert pf.get(1).t == 1
        assert pf.get(2).t == 2
    assert built[0] == 0 and 0 in built[2:], "rewind must rebuild t=0"


def test_prefetcher_close_joins_thread():
    pf = RoundPrefetcher(_ctx, start=0, stop=5)
    assert pf.get(0).t == 0
    pf.close()
    pf.close()                                  # idempotent
    assert not pf._thread.is_alive()


# ---------------------------------------------------------------------------
# ClusterRunner pipeline modes: bit-identity, all modes, dead worker
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("latency", [
    lambda: LognormalTailLatency(seed=3, tail_prob=0.3, tail_scale=25.0),
    lambda: make_latency("bursty", seed=3)], ids=["lognormal", "bursty"])
def test_pipeline_modes_bit_identical_under_stragglers(binary_data, latency):
    """Every pipeline mode == train_reference on the observed trace, and
    (order-independent latencies) all modes observe the SAME trace and
    produce the SAME weights as the sequential engine, under heavy tails
    and under multi-round straggler bursts."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    ws, traces = {}, {}
    for mode in PIPELINE_MODES:
        lat = latency()
        runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat,
                               pipeline=mode,
                               encode_cost_s=0.2, decode_cost_s=0.1)
        ws[mode] = np.asarray(runner.run(10))
        traces[mode] = [tuple(map(int, r.survivors))
                        for r in runner.records.values()]
        w_ref, _ = protocol.train_reference(
            cfg, jax.random.PRNGKey(7), x, y, iters=10,
            survivor_fn=runner.survivor_fn())
        assert (ws[mode] == np.asarray(w_ref)).all(), mode
    for mode in PIPELINE_MODES[1:]:
        assert (ws[mode] == ws["off"]).all(), mode
        assert traces[mode] == traces["off"], mode


def test_pipeline_minibatch_multiclass_bit_identical():
    """Mini-batch draws ride the prefetcher: the prefetched batch indices
    must reproduce make_schedule's derivations exactly."""
    x, y = synthetic.multiclass_mnist_like(jax.random.PRNGKey(42), m=240,
                                           d=20, c=3)
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1, c=3, batch_rows=16)
    lat = LognormalTailLatency(seed=5, tail_prob=0.2, tail_scale=10.0)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat,
                           pipeline="full")
    w = runner.run(8)
    w_ref, _ = protocol.train_reference(cfg, jax.random.PRNGKey(7), x, y,
                                        iters=8,
                                        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()


def test_pipeline_full_rides_through_mid_run_dead_worker(binary_data):
    """Pipelined-vs-sequential bit-identity with a worker dying mid-run
    (within the erasure tolerance): same trace, same weights, and both
    equal the reference."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)        # threshold 7
    ws = {}
    for mode in ("off", "full"):
        lat = DeadWorkerLatency(DeterministicLatency(base=1.0, skew=0.1),
                                deaths={5: 4})
        runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y, lat,
                               pipeline=mode,
                               encode_cost_s=0.2, decode_cost_s=0.1)
        ws[mode] = np.asarray(runner.run(12))
        assert all(5 not in set(map(int, r.survivors))
                   for t, r in runner.records.items() if t >= 4)
        w_ref, _ = protocol.train_reference(
            cfg, jax.random.PRNGKey(7), x, y, iters=12,
            survivor_fn=runner.survivor_fn())
        assert (ws[mode] == np.asarray(w_ref)).all(), mode
    assert (ws["full"] == ws["off"]).all()


def test_pipeline_full_survives_checkpoint_restore(binary_data):
    """A starved round under pipeline=full restores + replays: the
    prefetcher rewinds and the replayed contexts are identical, so the
    resilient run still completes with the usual guarantees."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    lat = DeadWorkerLatency(LognormalTailLatency(seed=5),
                            deaths={0: 4, 1: 4})
    runner = ClusterRunner(cfg, jax.random.PRNGKey(9), x, y, lat,
                           round_timeout_s=60.0, pipeline="full")
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_write=False)
        w = runner.run_resilient(12, mgr, checkpoint_every=2)
    assert runner.restarts == 1
    assert len(runner.records) == 12
    assert w.shape == (x.shape[1],)
    assert runner.records[11].n_responders >= cfg.threshold


def test_streaming_prediction_hits_under_stable_order(binary_data):
    """Deterministic latencies -> a constant responder order -> the
    subset prediction hits from round 2 on (round 0 has no history; round
    1's plan is built by the prefetch thread before round 0 completes)."""
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                           DeterministicLatency(base=1.0, skew=0.1),
                           pipeline="streaming")
    runner.run(8)
    stats = runner.wait_stats()
    # "streaming" without prefetch builds the plan inline from the last
    # observed order: only round 0 can miss
    assert stats["rounds"]["streamed"] >= 7.0


def test_pipeline_rejects_unknown_mode(binary_data):
    x, y = binary_data
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    with pytest.raises(AssertionError):
        ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                      DeterministicLatency(), pipeline="bogus")
