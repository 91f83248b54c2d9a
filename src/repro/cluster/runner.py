"""ClusterRunner: coded training driven by the cluster runtime.

Division of labor (DESIGN.md §7): the scheduler moves messages and time;
ALL gradient numerics run through the exact round/update functions
train()/train_reference() use, with the decode matrix and responder order
observed from the runtime.  In the in-process simulation the whole round is
``engine.round_fn`` on the master; over the socket transport real worker
processes evaluate f(X̃_i, W̃_i) and their deserialized payloads feed
``engine.update_fn`` — the same decode+step the simulated round composes.
Consequence: a ClusterRunner run — simulated or live — is BIT-IDENTICAL to
``engine.train_reference`` replaying the same responder trace
(tests/test_cluster.py, tests/test_socket_cluster.py), so the cluster
layer can never silently change training semantics, only timing and
placement.

The ``engine`` knob (DESIGN.md §14) picks the coded-arithmetic backend
behind those hooks: ``"exact"`` (default) is the quantized field protocol
above; ``"alcc"`` swaps in ``protocol/alcc_engine`` — real-valued Lagrange
coding with Gaussian analog masks and a least-squares decode.  The runner
code is shared; only three things change: weight shares ship as float32
(v2-only FROUND/FRESULT wire frames on the socket transport), the decode
hooks take the responder ORDER instead of an int32 decode matrix, and
every decode's condition number / error budget / fallback flag is
collected into ``wait_stats()["alcc"]``, the ``cpml_alcc_*`` metrics and
``alcc_decode`` trace instants.  The replay invariant becomes two-tier:
sim runs stay bit-identical to ``alcc_engine.train_reference``; socket
runs agree within the decode error budget (XLA-vs-BLAS float32 summation
order).  Exact-only machinery — ``pipeline`` modes, ``masters > 1``,
spares/joins — is refused at construction.

Resilience integration (runtime/resilience.py):

  * HeartbeatMonitor — results/acks feed it on the SIMULATED clock; workers
    that stop heartbeating (dead) drop out of the dispatch set, and known
    stragglers are speculatively excluded from dispatch while the fast set
    STRICTLY exceeds the recovery threshold (exact coverage leaves no slack
    for an undetected death).
  * ResilientLoop + CheckpointManager — ``run_resilient(...)``
    checkpoints every k rounds; a round that starves (fewer than
    ``threshold`` responses inside the timeout) raises ClusterDecodeError,
    the loop restores the last checkpoint, and the ``on_restore`` hook
    reprovisions dead workers (latency.revive + monitor.revive) before
    replay — mid-run worker death costs a rollback, not the run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time as _time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.latency import LatencyModel
from repro.cluster.master_group import MasterGroup
from repro.cluster.membership import ClusterMembership, MembershipView
from repro.cluster.messages import (
    MASTER,
    PROVISION_ROUND,
    SHUTDOWN_ROUND,
    EncodeShare,
    Epoch,
    Heartbeat,
    Join,
    worker_endpoint,
)
from repro.cluster.pipeline import PIPELINE_MODES, RoundContext, RoundPrefetcher
from repro.cluster.scheduler import ClusterDecodeError, EventScheduler, RoundTrace
from repro.cluster.wire import WIRE_V2
from repro.cluster.transport import Transport
from repro.core.protocol import alcc_engine, decode, engine
from repro.core.protocol.config import CPMLConfig

# the runner's engine is pluggable (DESIGN.md §14): "exact" is the field
# protocol (bit-identical decode), "alcc" the float backend (least-squares
# decode with a tracked error budget).  Both expose the same hook factories.
ENGINES = {"exact": engine, "alcc": alcc_engine}
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import phase
from repro.runtime.resilience import HeartbeatMonitor, ResilientLoop


def wait_summary(a) -> dict[str, float]:
    """mean/p50/p95/total of a wait-time series (zeroed when empty).

    The one aggregation both ClusterRunner and MPCClusterRunner report in
    wait_stats, so their keys can never disagree.  An
    EMPTY series — no completed rounds, or an all-starved trace — returns a
    well-formed all-zero summary: numpy would warn and NaN on a mean over
    nothing, and inf placeholders poison downstream ratio math (inf/inf)
    (pinned by tests/test_obs.py)."""
    a = np.asarray(a, dtype=float)
    if a.size == 0:
        return {"mean": 0.0, "p50": 0.0, "p95": 0.0, "total": 0.0}
    return {"mean": float(a.mean()), "p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)), "total": float(a.sum())}


def await_worker_acks(transport: Transport, clock_fn, expect,
                      monitor, timeout_s: float,
                      control: list | None = None) -> None:
    """Block until every worker in ``expect`` has acked provisioning with a
    Heartbeat (shared by ClusterRunner and MPCClusterRunner, so both
    protocols start their wall clocks after worker warmup).

    ``expect`` is an int (the historical contract: workers 0..n-1) or an
    explicit set of slots — elastic provisioning waits on exactly the
    subset it just shipped shares to, e.g. a single mid-run joiner.
    ``control`` (when given) collects JOIN frames drained off the master
    inbox here instead of dropping them — a late joiner may announce itself
    while the initial fleet is still acking.
    """
    expect = (set(range(expect)) if isinstance(expect, int)
              else {int(w) for w in expect})
    deadline = clock_fn() + timeout_s
    acked: set[int] = set()
    while not expect <= acked:
        nxt = transport.next_delivery(MASTER)
        if nxt is None:
            if clock_fn() >= deadline:
                raise TimeoutError(
                    f"workers never acked provisioning: "
                    f"{sorted(expect - acked)}")
            continue
        for at, msg in transport.recv(MASTER, nxt):
            if isinstance(msg, Heartbeat):
                if monitor is not None:
                    monitor.heartbeat(msg.worker, now=at)
                acked.add(msg.worker)
            elif isinstance(msg, Join) and control is not None:
                control.append((at, msg))


@dataclasses.dataclass
class RoundRecord:
    """Per-round outcome: who decoded, and what each wait policy cost.

    A thin VIEW over the scheduler's RoundTrace (DESIGN.md §11): every
    timing/wire number is read from the one trace the scheduler observed —
    the same source the flight recorder's spans are emitted from — so
    wait_stats, the recorder, and the benches can never drift apart.  The
    record adds only what the runner itself decided: the decode order used,
    and the replay/pipeline flags.
    """
    round: int
    trace: RoundTrace            # the single timing source for this round
    survivors: np.ndarray        # decode order used (first `threshold`)
    replayed: bool = False       # True when re-run after a restore
    prefetched: bool = False     # W-independent half built ahead of time
    streamed: bool = False       # decode was the incremental fold (hit)

    @property
    def n_responders(self) -> int:           # responses in by loop exit
        return len(self.trace.responders)

    @property
    def dispatched(self) -> np.ndarray:
        return self.trace.dispatched

    @property
    def coded_wait_s(self) -> float:         # wait-for-fastest-T
        return self.trace.coded_wait_s

    @property
    def all_wait_s(self) -> float:           # wait-for-all (inf = dead)
        return self.trace.all_wait_s

    @property
    def encode_s(self) -> float:             # master encode, critical path
        return self.trace.encode_s

    @property
    def decode_s(self) -> float:             # master decode+step
        return self.trace.decode_s

    @property
    def tx_bytes(self) -> int:               # wire accounting (zeros on
        return self.trace.tx_bytes           # the simulated backend)

    @property
    def rx_bytes(self) -> int:
        return self.trace.rx_bytes

    @property
    def tx_frames(self) -> int:
        return self.trace.tx_frames

    @property
    def rx_frames(self) -> int:
        return self.trace.rx_frames

    @property
    def critical_path_s(self) -> float:
        return self.trace.critical_path_s


class ClusterRunner:
    """Drives ``iters`` protocol rounds through the event scheduler.

    One runner = one training run (like engine.train); ``run()`` starts
    from the initial weights every call.

    Two transports, one round loop (DESIGN.md §7):

      * ``latency`` given — in-process simulation: the scheduler enacts the
        workers and the runner computes the whole round on the master via
        ``engine.round_fn`` with the observed responder order.
      * ``latency=None`` + a real transport (socket_transport.py) — actual
        worker processes evaluate f(X̃_i, W̃_i); the runner encodes + ships
        the round's weight shares, decodes the first-``threshold`` received
        payloads via ``engine.update_fn``, and the wall clock replaces the
        simulated clock.  ``provision()`` must run once before rounds.

    Pipelining (DESIGN.md §9) — ``pipeline`` selects how much master-side
    work leaves the critical path; every mode stays bit-identical to
    ``train_reference`` on the observed trace:

      * ``"off"``       — the sequential loop: encode -> dispatch -> wait ->
        decode, all serial.
      * ``"prefetch"``  — a RoundPrefetcher thread builds round t+1's
        W-independent context (key split, fresh masks + their encoded
        contribution, batch draw, decode-coefficient prefixes) while round
        t is in flight; the critical path keeps only the W-dependent encode
        half.
      * ``"streaming"`` — decode.StreamingDecoder folds each share into the
        Lagrange reconstruction as it arrives (predicted-order coefficient
        columns); after the threshold-th arrival only ONE fold remains.
      * ``"full"``      — both.

    On a real transport the overlap is EXECUTED (threads + incremental
    folds, components measured on the wall clock); in simulation it is
    MODELED — ``encode_cost_s``/``decode_cost_s`` are charged to the
    SimClock, scaled down by what each mode hides: prefetch leaves the
    K/(K+T) data-row fraction of the encode; streaming leaves 1/threshold
    of the decode on rounds whose subset prediction hits, and the FULL
    decode cost on misses (the fallback batch decode a real decoder pays).

    Knobs beyond the common cfg/latency/transport:

      * ``engine`` — ``"exact"`` (field protocol, default) or ``"alcc"``
        (real-valued coding, DESIGN.md §14; see the module docstring for
        what changes — and what is refused — under ALCC).
      * ``eta`` — step size; None auto-tunes 1/L by power iteration.
      * ``round_timeout_s`` / ``heartbeat_timeout_s`` — starvation and
        failure-detector walls (sim clock when simulated, wall clock live).
      * ``straggler_factor`` / ``exclude_stragglers`` — EWMA-based
        speculative exclusion of known-slow workers while the fast set
        strictly exceeds the recovery threshold.
      * ``collect_all`` — hold rounds open past the decode so the
        wait-for-all counterfactual is measured on the same trace.
      * ``spares`` / ``masters`` / ``join_schedule`` — elastic membership
        and the sharded master role (DESIGN.md §13, exact engine only).
      * ``recorder`` / ``metrics`` — the §11 flight recorder hooks; free
        when None.
    """

    def __init__(self, cfg: CPMLConfig, key, x, y,
                 latency: LatencyModel | None = None, *,
                 eta: float | None = None,
                 transport: Transport | None = None,
                 round_timeout_s: float = math.inf,
                 heartbeat_timeout_s: float = math.inf,
                 straggler_factor: float = 3.0,
                 master_overhead_s: float = 0.0,
                 exclude_stragglers: bool = True,
                 collect_all: bool = False,
                 pipeline: str = "off",
                 encode_cost_s: float = 0.0,
                 decode_cost_s: float = 0.0,
                 recorder=None,
                 metrics: MetricsRegistry | None = None,
                 spares: int = 0,
                 masters: int = 1,
                 join_schedule: dict[int, int] | None = None,
                 engine: str = "exact"):
        # heartbeat_timeout_s defaults to inf: in the simulation, true
        # deaths surface as round starvation (-> mark_failed) and slowness
        # as the EWMA straggler stat; a finite timeout models a gossip-style
        # failure detector and must exceed the worst healthy round, or a
        # single long round makes healthy-but-quiet workers look dead.
        assert pipeline in PIPELINE_MODES, (
            f"pipeline={pipeline!r} not in {PIPELINE_MODES}")
        assert engine in ENGINES, f"engine={engine!r} not in {set(ENGINES)}"
        self.engine_name = engine
        self.eng = ENGINES[engine]
        if engine == "alcc":
            # the float engine keeps the round loop but not the exact-only
            # machinery: pipelining splits a FIELD matmul, and the sharded
            # master / elastic spare points rely on bit-identical re-encode
            assert pipeline == "off", "pipeline modes are exact-engine only"
            assert masters == 1 and spares == 0 and not join_schedule, (
                "sharded masters / elastic membership are exact-engine only")
        # Elastic membership (DESIGN.md §13): ``spares`` extra Lagrange
        # evaluation points are encoded up front — the coding scheme's
        # points are consecutive, so extending N to N+spares leaves shares
        # 0..N-1 and every decode over them bit-identical to the fixed-N
        # scheme.  A spare slot carries no live worker until a JOIN (late
        # Join frame over the wire, or ``join_schedule={slot: round}`` in
        # simulation) or a LEAVE replacement admits it.  spares == 0 and no
        # join schedule keeps today's fixed-fleet behavior exactly.
        self.base_n = cfg.N
        if spares:
            cfg = dataclasses.replace(cfg, N=cfg.N + spares)
        self.cfg = cfg
        self.elastic = spares > 0 or bool(join_schedule)
        # Sharded master group (DESIGN.md §13): S > 1 splits the master's
        # per-round encode + streaming-decode over contiguous d-slices.
        # Bit-identical (randomness at full shape); used on the distributed
        # paths — the in-process simulation traces the whole round as one
        # jitted function, where sharding the master has nothing to shard.
        self.masters = int(masters)
        self.master_group = (MasterGroup(cfg, self.masters)
                             if self.masters > 1 else None)
        ksetup, self.kloop = jax.random.split(key)
        self.state = self.eng.setup(
            cfg, ksetup, x, y,
            dataset_encoder=(self.master_group.encode_dataset
                             if self.master_group is not None else None))
        self.eta = (self.eng.lipschitz_eta(self.state.xq_real)
                    if eta is None else eta)
        if engine == "alcc":
            # every least-squares decode appends its conditioning / error-
            # budget info here; wait_stats["alcc"] and the obs instants
            # read it back per round
            self.alcc_info: list[dict] = []
            self._round = self.eng.round_fn(cfg, self.state, self.eta,
                                            info_sink=self.alcc_info)
            self._update = self.eng.update_fn(cfg, self.state, self.eta,
                                              info_sink=self.alcc_info)
        else:
            self.alcc_info = None
            self._round = self.eng.round_fn(cfg, self.state, self.eta)
            self._update = self.eng.update_fn(cfg, self.state, self.eta)
        self._round_split = self.eng.round_fn_split(cfg, self.state, self.eta)
        self._update_parts = self.eng.update_from_parts_fn(cfg, self.state,
                                                           self.eta)
        self.pipeline = pipeline
        self.encode_cost_s = encode_cost_s
        self.decode_cost_s = decode_cost_s
        self._w_shape = (x.shape[1], cfg.c)       # internal w2 shape
        self._prefetcher: RoundPrefetcher | None = None
        self._last_order: np.ndarray | None = None    # prediction source
        self.latency = latency
        self.round_timeout_s = round_timeout_s
        self.exclude_stragglers = exclude_stragglers
        self.collect_all = collect_all
        self.scheduler = EventScheduler(cfg.N, latency, transport,
                                        master_overhead_s=master_overhead_s,
                                        recorder=recorder)
        # flight recorder (DESIGN.md §11): bound to the SCHEDULER's clock so
        # sim and wall runs emit the same span shape through the same call
        # sites; the default NullRecorder keeps every site a no-op.
        self.obs = self.scheduler.obs
        self.obs.bind_clock(self.scheduler.time.now)
        # metrics are always on, like the wire byte counters they aggregate
        # (a handful of float ops per round; gated with the recorder in
        # bench_cluster's trace_overhead entry)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._init_metrics()
        if self.distributed and math.isinf(round_timeout_s):
            # a real cluster must be able to give up on silence
            self.round_timeout_s = 300.0
        self.monitor = HeartbeatMonitor(self.base_n,
                                        timeout_s=heartbeat_timeout_s,
                                        straggler_factor=straggler_factor,
                                        now=self.scheduler.clock)
        # membership starts as the base fleet; the spare slots (base_n..N-1)
        # hold pre-encoded shares awaiting admission.  The scheduler reads
        # its default worker set off the live membership from here on.
        self.membership = ClusterMembership(
            range(self.base_n), monitor=self.monitor,
            spares=range(self.base_n, cfg.N))
        self.scheduler.bind_membership(self.membership)
        for w, at_round in (join_schedule or {}).items():
            self.membership.schedule_join(w, at_round)
        self.w2 = self.eng._w_internal(cfg, self.state.w)
        self.records: dict[int, RoundRecord] = {}
        self.traces: dict[int, RoundTrace] = {}
        self.restarts = 0

    @property
    def distributed(self) -> bool:
        """True when real worker processes compute (socket transport)."""
        return self.latency is None

    # ------------------------------------------------------------------
    # Observability (DESIGN.md §11)
    # ------------------------------------------------------------------

    def _init_metrics(self) -> None:
        m = self.metrics
        self._m_rounds = m.counter(
            "cpml_rounds_total", "completed training rounds")
        self._m_starved = m.counter(
            "cpml_starved_rounds_total",
            "rounds with fewer than threshold responses in the timeout")
        self._m_excluded = m.counter(
            "cpml_straggler_exclusions_total",
            "worker-rounds speculatively excluded from dispatch")
        self._m_marked_dead = m.counter(
            "cpml_heartbeat_misses_total",
            "workers marked dead after round-timeout silence")
        self._m_prefetch = m.counter(
            "cpml_prefetch_hits_total",
            "rounds served from a prefetched W-independent context")
        self._m_folds = m.counter(
            "cpml_stream_folds_total", "eager streaming-decoder folds")
        self._m_streamed = m.counter(
            "cpml_streamed_rounds_total",
            "rounds decoded by the incremental fold (prediction hits)")
        self._m_tx = m.counter(
            "cpml_wire_tx_bytes_total", "wire bytes enqueued during rounds")
        self._m_rx = m.counter(
            "cpml_wire_rx_bytes_total", "wire bytes received during rounds")
        self._m_wait = m.histogram(
            "cpml_round_wait_seconds",
            "dispatch to threshold-th arrival, per round")
        self._m_cp = m.histogram(
            "cpml_round_critical_path_seconds",
            "encode + wait + decode, per round")
        self._m_alive = m.gauge(
            "cpml_workers_alive", "dispatchable workers at last round")
        self._m_epoch = m.gauge(
            "cpml_epoch", "membership epoch at the last round fence")
        self._m_members = m.gauge(
            "cpml_members_alive", "member slots at the last round fence")
        self._m_joins = m.counter(
            "cpml_member_joins_total", "workers admitted mid-run")
        self._m_leaves = m.counter(
            "cpml_member_leaves_total", "members permanently retired")
        self._m_warm = m.gauge(
            "cpml_xla_warm_compile_seconds",
            "max worker-reported XLA warm-compile wall (needs tracing + v2 "
            "wire)")
        if self.engine_name == "alcc":
            self._m_alcc_cond = m.gauge(
                "cpml_alcc_decode_cond",
                "condition number of the last round's least-squares decode")
            self._m_alcc_budget = m.gauge(
                "cpml_alcc_error_budget",
                "a-priori absolute decode-error bound of the last round "
                "(cond * eps32 * max|evaluation|)")
            self._m_alcc_fallback = m.counter(
                "cpml_alcc_decode_fallbacks_total",
                "rounds decoded by the overdetermined all-responder "
                "fallback (square system over cond_max)")

    def _observe_round(self, t: int, trace: RoundTrace,
                       rec: RoundRecord) -> None:
        """Emit the round's derived spans + update the metrics registry.

        Runs while the ``round`` span is still open, so the derived spans
        nest under it.  The encode/wait/decode intervals are reconstructed
        from the SAME RoundTrace fields wait_stats aggregates — on the sim
        clock they are the pre/post charges, on the wall clock the measured
        components — which is what makes the recorder and wait_stats
        reconcile exactly (tests/test_obs.py, bench trace gates).
        """
        obs = self.obs
        if obs.enabled:
            if trace.encode_s > 0:
                obs.add_span("encode", trace.t_start - trace.encode_s,
                             trace.t_start, round=t)
            obs.add_span("wait", trace.t_start, trace.t_first_R, round=t,
                         responders=rec.n_responders)
            t_ready = trace.t_ready
            if math.isfinite(t_ready) and trace.decode_s > 0:
                obs.add_span("decode", t_ready - trace.decode_s, t_ready,
                             round=t, streamed=rec.streamed)
            for w, spans in trace.worker_traces.items():
                obs.add_process_spans(f"worker{int(w)}", spans, round=t)
        self._m_rounds.inc()
        if self.alcc_info:
            # the decode that just ran appended its conditioning info
            info = self.alcc_info[-1]
            self._m_alcc_cond.set(float(info["cond"]))
            self._m_alcc_budget.set(float(info["abs_err_budget"]))
            if info["fallback"]:
                self._m_alcc_fallback.inc()
            self.obs.instant("alcc_decode", round=t,
                             cond=float(info["cond"]),
                             err_budget=float(info["abs_err_budget"]),
                             fallback=bool(info["fallback"]))
        if rec.prefetched:
            self._m_prefetch.inc()
        if rec.streamed:
            self._m_streamed.inc()
        self._m_tx.inc(trace.tx_bytes)
        self._m_rx.inc(trace.rx_bytes)
        self._m_wait.observe(trace.coded_wait_s)
        self._m_cp.observe(trace.critical_path_s)
        self._m_alive.set(len(self._alive(self.scheduler.clock)))
        for spans in trace.worker_traces.values():
            for item in spans:
                # the worker attaches its provisioning-window XLA compile
                # to its first traced result (launch/cpml_worker.py)
                if item and item[0] == "warm_compile" and len(item) == 3:
                    self._m_warm.set(max(self._m_warm.value,
                                         float(item[2]) - float(item[1])))

    # ------------------------------------------------------------------
    # Pipeline plumbing (DESIGN.md §9)
    # ------------------------------------------------------------------

    @property
    def prefetching(self) -> bool:
        return self.pipeline in ("prefetch", "full")

    @property
    def streaming(self) -> bool:
        return self.pipeline in ("streaming", "full")

    def _predicted_order(self) -> np.ndarray | None:
        """Forecast next round's responder order: last round's arrivals.

        Read racily by the prefetch thread — the prediction only steers
        which decode coefficients are precomputed/folded eagerly, never
        which decode runs, so staleness costs a fallback, not correctness.
        """
        return self._last_order

    def _build_ctx(self, t: int, iters: int) -> RoundContext:
        """Round t's W-independent context (runs on the prefetch thread)."""
        cfg = self.cfg
        key_t = self.eng.round_key(self.kloop, t)
        kq, mask_shares = self.eng.round_mask_context(cfg, key_t, self._w_shape)
        bidx = next_np = None
        if cfg.batch_rows is not None:
            bidx = self.eng.draw_batch(cfg, self.kloop, iters,
                                     self.state.mk, t)
            if self.distributed and t + 1 < iters:
                # round t+1's indices ride in round t's dispatch so the
                # workers pre-slice their coded sub-batch while idle
                next_np = np.asarray(self.eng.draw_batch(
                    cfg, self.kloop, iters, self.state.mk, t + 1))
        plan = (decode.prefix_decode_plan(cfg, self._predicted_order())
                if self.streaming else None)
        # racy epoch read (prefetch thread): a transition between build and
        # use is caught at the fence, which invalidates only the plan
        return RoundContext(t=t, kq=kq,
                            mask_shares=np.asarray(mask_shares),
                            batch_idx=bidx, plan=plan, next_batch=next_np,
                            epoch=self.membership.epoch)

    def _pipeline_scope(self, iters: int):
        """Context manager owning the prefetch thread for one training run."""
        if not self.prefetching:
            return contextlib.nullcontext()
        self._prefetcher = RoundPrefetcher(
            lambda t: self._build_ctx(t, iters), start=0, stop=iters,
            recorder=self.obs)

        @contextlib.contextmanager
        def scope():
            try:
                yield
            finally:
                self._prefetcher.close()
                self._prefetcher = None

        return scope()

    def _sim_charges(self) -> tuple[float, float]:
        """(pre_s, post_s) master-side charges for the SimClock, scaled by
        what the active pipeline mode hides (class docstring).  post_s is
        the prediction-HIT fold; step_round tops it up to the full decode
        cost on rounds whose subset prediction missed."""
        cfg = self.cfg
        pre = self.encode_cost_s
        if self.prefetching:
            pre *= cfg.K / (cfg.K + cfg.T)    # mask rows precomputed
        post = self.decode_cost_s
        if self.streaming:
            post /= cfg.threshold             # one fold left after arrival
        return pre, post

    # ------------------------------------------------------------------
    # Distributed-mode provisioning: one-time worker state over the wire
    # ------------------------------------------------------------------

    def provision(self, workers=None, timeout_s: float = 60.0) -> None:
        """Ship each worker its coded dataset share + static round context.

        Sent as an EncodeShare with ``round == PROVISION_ROUND``; the worker
        acks with a Heartbeat once its share is loaded, and rounds only
        start after every dispatched worker has acked (so round-0 timing
        does not absorb worker warmup).

        ``workers=None`` provisions the current members (the historical
        whole-fleet call); an explicit subset provisions exactly those
        slots — a mid-run joiner picking up its pre-encoded spare share, or
        a resilient-restore respawn reprovisioning one dead slot.
        """
        assert self.distributed, "provision() is for real transports only"
        if workers is None:
            workers = list(self.membership.view().members)
        workers = [int(w) for w in workers]
        wall0 = _time.perf_counter()
        with self.obs.span("provision", workers=len(workers)):
            tr = self.scheduler.transport
            x_shares = np.asarray(self.state.x_shares)
            cbar = self.eng.poly_coeffs(self.cfg)
            if self.engine_name == "alcc":
                # float engine: no quantization scales to ship; the worker
                # selects its float round fn off the "protocol" marker
                cfg_kw = {"N": self.cfg.N, "K": self.cfg.K, "T": self.cfg.T,
                          "r": self.cfg.r, "c": self.cfg.c,
                          "sigma": self.cfg.sigma,
                          "batch_rows": self.cfg.batch_rows}
            else:
                cfg_kw = {"N": self.cfg.N, "K": self.cfg.K, "T": self.cfg.T,
                          "r": self.cfg.r, "c": self.cfg.c, "lx": self.cfg.lx,
                          "lw": self.cfg.lw, "lc": self.cfg.lc, "p": self.cfg.p,
                          "batch_rows": self.cfg.batch_rows}
            now = self.scheduler.clock
            for w in workers:
                payload = {"cfg": cfg_kw, "x_share": x_shares[w],
                           "cbar": cbar,
                           # ask the workers to record + piggy-back their
                           # own per-round spans (v2 wire only; a v1 peer
                           # drops the field)
                           "trace": bool(self.obs.enabled)}
                if self.engine_name == "alcc":
                    payload["protocol"] = "alcc"
                tr.send(worker_endpoint(w),
                        EncodeShare(PROVISION_ROUND, w, payload),
                        at=now)
            await_worker_acks(tr, lambda: self.scheduler.clock, set(workers),
                              self.monitor, timeout_s,
                              control=self.scheduler.control_inbox)
        self.metrics.gauge(
            "cpml_provision_seconds",
            "wall seconds from provisioning dispatch to the last worker "
            "ack (includes worker XLA warmup)").set(
                _time.perf_counter() - wall0)

    def shutdown_workers(self) -> None:
        """Ask every live member's process to exit its serve loop (departed
        slots' processes are already dead; never-admitted spares have no
        process to stop)."""
        assert self.distributed
        now = self.scheduler.clock
        for w in self.membership.view().members:
            self.scheduler.transport.send(
                worker_endpoint(w), EncodeShare(SHUTDOWN_ROUND, w), at=now)

    # ------------------------------------------------------------------
    # Elastic membership: the per-round epoch fence (DESIGN.md §13)
    # ------------------------------------------------------------------

    def _broadcast_epoch(self, view: MembershipView, t: int) -> None:
        """Fan the new epoch out to the live members (informational — the
        fence is master-side).  Epoch is a wire v2 frame; v1 peers are
        skipped so their byte stream stays bit-identical to fixed-fleet."""
        if not self.distributed:
            return
        tr = self.scheduler.transport
        peer_version = getattr(tr, "peer_version", None)
        now = self.scheduler.clock
        for w in view.members:
            ep = worker_endpoint(w)
            if peer_version is not None and peer_version(ep) < WIRE_V2:
                continue
            tr.send(ep, Epoch(view.epoch, view.members, t), at=now)

    def _admit(self, worker: int, t: int) -> None:
        """Admit one slot at the fence: distributed mode first provisions
        the joiner's pre-encoded spare share and waits for its ack, so a
        member is never dispatched before it can answer."""
        if self.distributed:
            t0 = self.scheduler.clock
            self.provision(workers=[worker], timeout_s=60.0)
            # the ack barrier (it includes the joiner's XLA warmup) stalls
            # round dispatch — credit the live fleet, whose only heartbeat
            # source is the per-round acks the stall suspended
            self.monitor.credit_stall(self.scheduler.clock - t0,
                                      now=self.scheduler.clock)
        now = self.scheduler.clock
        self.membership.admit(worker, t, now=now)
        self._m_joins.inc()
        self.obs.instant("member_join", round=t, worker=int(worker),
                         epoch=self.membership.epoch)

    def _membership_fence(self, t: int) -> MembershipView:
        """The round fence: apply every due membership transition, then
        snapshot.  Round t's dispatch set, decode matrix and DecodePlan all
        derive from the ONE view returned here — a transition can never mix
        two fleets inside a round.  Non-elastic runs take the no-transition
        fast path and keep the historical per-round speculative exclusion
        semantics bit-identically."""
        if self.elastic:
            now = self.scheduler.clock
            # JOIN requests drained off the wire (socket: late HELLO+Join)
            for _, msg in self.scheduler.control_inbox:
                self.membership.schedule_join(msg.worker, msg.at_round)
            self.scheduler.control_inbox.clear()
            span = None
            pre_epoch = self.membership.epoch
            # LEAVE: a member the failure detector declared dead is retired
            # for good (not re-excluded every round); in simulation a spare
            # immediately replaces it (the scheduler enacts the new slot) —
            # on a real transport replacements arrive as JOINs from actual
            # late worker processes.
            for w in list(self.membership.view().members):
                if w in self.monitor.workers and self.monitor.is_dead(
                        w, now=now):
                    if span is None:
                        span = self.obs.begin("membership_transition",
                                              round=t)
                    self.membership.leave(w, t, now=now)
                    self._m_leaves.inc()
                    self.obs.instant("member_leave", round=t, worker=int(w),
                                     epoch=self.membership.epoch)
                    if not self.distributed:
                        spare = self.membership.take_spare()
                        if spare is not None:
                            self._admit(spare, t)
            for w in self.membership.due_joins(t):
                if span is None:
                    span = self.obs.begin("membership_transition", round=t)
                self._admit(w, t)
            view = self.membership.view()
            if view.epoch != pre_epoch:
                self._broadcast_epoch(view, t)
            if span is not None:
                self.obs.end(span)
        else:
            view = self.membership.view()
        self._m_epoch.set(view.epoch)
        self._m_members.set(len(view))
        return view

    # ------------------------------------------------------------------
    # Dispatch-set policy: monitor-alive workers, minus known stragglers
    # while the fast set strictly exceeds the recovery threshold.
    # ------------------------------------------------------------------

    def _alive(self, now: float) -> np.ndarray:
        return np.array(
            [i for i in self.monitor.workers
             if not self.monitor.is_dead(i, now=now)],
            dtype=np.int64)

    def dispatch_set(self, view: MembershipView | None = None) -> np.ndarray:
        now = self.scheduler.clock
        alive = self._alive(now)
        if view is not None:
            # epoch fence: only this round's membership snapshot dispatches
            # (the monitor tracks members exactly, so this is a no-op guard
            # against a transition racing between fence and dispatch)
            alive = np.asarray([w for w in alive if w in view],
                               dtype=np.int64)
        if self.exclude_stragglers:
            fast = self.monitor.survivors(now=now)
            if view is not None:
                fast = np.asarray([w for w in fast if w in view],
                                  dtype=np.int64)
            # STRICTLY more than threshold: speculative exclusion must leave
            # slack, because the fast set can still contain an undetected
            # dead worker — dispatching exactly `threshold` workers means a
            # single silent failure starves the round.
            if len(fast) > self.cfg.threshold:
                self._m_excluded.inc(len(alive) - len(fast))
                return fast
        return alive

    # ------------------------------------------------------------------
    # One round
    # ------------------------------------------------------------------

    def step_round(self, t: int, iters: int, replayed: bool = False
                   ) -> RoundTrace:
        """One traced protocol round: the ``round`` span brackets the whole
        critical path, the derived encode/wait/decode spans and metrics are
        emitted while it is open (so they nest), and a starved round leaves
        an instant marker + counter bump before the error propagates to the
        resilient loop."""
        with phase("round", self.obs, round=t, replayed=replayed):
            try:
                trace = self._step_round_inner(t, iters, replayed)
                self._observe_round(t, trace, self.records[t])
                return trace
            except ClusterDecodeError:
                self.obs.instant("starved", round=t)
                self._m_starved.inc()
                raise

    def _step_round_inner(self, t: int, iters: int, replayed: bool = False
                          ) -> RoundTrace:
        cfg = self.cfg
        with phase("fence"):
            view = self._membership_fence(t)
            workers = self.dispatch_set(view)
        if len(workers) < cfg.threshold:
            raise ClusterDecodeError(
                f"round {t}: only {len(workers)} dispatchable workers < "
                f"recovery threshold {cfg.threshold}")
        ctx = (self._prefetcher.get(t)
               if self._prefetcher is not None else None)
        if ctx is not None and ctx.epoch != view.epoch:
            # the context was prefetched under an older fleet: only its
            # DecodePlan referenced that fleet (predicted responders) — the
            # key split, masks and batch are pure functions of (kloop, t)
            # and stay valid.  Drop the plan; the decode falls back to the
            # observed-order path (a performance miss, never a wrong decode)
            ctx.plan = None
            ctx.epoch = view.epoch
            self.obs.instant("prefetch_epoch_invalidated", round=t,
                             epoch=view.epoch)
        with phase("round_key"):
            key_t = (None if ctx is not None
                     else self.eng.round_key(self.kloop, t))
        # the subset the streaming decode would fold against this round
        # (ctx.plan when prefetched — possibly one round staler — else the
        # last observed order); used for the decoder plan in distributed
        # mode and for honest streamed-flag reporting in simulation
        pred_subset = None
        if self.streaming:
            if ctx is not None and ctx.plan is not None:
                pred_subset = ctx.plan.subset
            elif ctx is None:
                pred = self._predicted_order()
                if pred is not None and len(pred) >= cfg.threshold:
                    pred_subset = frozenset(
                        int(w) for w in pred[: cfg.threshold])
        if ctx is not None:
            bidx = ctx.batch_idx
        else:
            bidx = (self.eng.draw_batch(cfg, self.kloop, iters,
                                      self.state.mk, t)
                    if cfg.batch_rows is not None else None)
        payloads = None
        enc_t0 = _time.perf_counter()
        if self.distributed:
            # encode THIS round's weight shares and ship one to each worker;
            # field elements are exact int32, so the share a worker process
            # receives is bit-identical to the one the in-process round
            # would have traced from the same key.  With a prefetched ctx
            # only the W-dependent half runs here (DESIGN.md §9).
            if self.master_group is not None:
                # sharded masters: each of the S masters encodes its own
                # contiguous d-slice (bit-identical: randomness full-shape)
                w_shares = (self.master_group.encode_round_shares_split(
                                ctx.kq, ctx.mask_shares, self.w2)
                            if ctx is not None else
                            self.master_group.encode_round_shares(
                                key_t, self.w2))       # (N, d, c, r)
            elif ctx is not None:
                w_shares = np.asarray(self.eng.encode_round_shares_split(
                    cfg, ctx.kq, ctx.mask_shares, self.w2))  # (N, d, c, r)
            else:
                w_shares = np.asarray(self.eng.encode_round_shares(
                    cfg, key_t, self.w2))
            batch_np = None if bidx is None else np.asarray(bidx)
            # round t+1's batch indices were drawn by the prefetch thread,
            # off the critical path (ctx.next_batch); sequential mode ships
            # none and the worker slices on receipt as before
            next_np = None if ctx is None else ctx.next_batch
            payloads = {int(w): {"w_share": w_shares[int(w)],
                                 "batch": batch_np,
                                 "next_batch": next_np}
                        for w in workers}
        encode_wall_s = _time.perf_counter() - enc_t0

        decoder = None
        on_result = None
        if self.streaming and self.distributed:
            plan = (ctx.plan if ctx is not None and ctx.plan is not None
                    else decode.prefix_decode_plan(
                        cfg, self._predicted_order()))
            decoder = (self.master_group.make_decoder(plan,
                                                      self._w_shape[0])
                       if self.master_group is not None
                       else decode.StreamingDecoder(cfg, plan))

            def on_result(w, payload, _d=decoder):
                self._m_folds.inc()
                self.obs.instant("fold", round=t, worker=int(w))
                _d.fold(w, payload)
        pre_s = post_s = 0.0
        if not self.distributed:
            pre_s, post_s = self._sim_charges()
        if self._prefetcher is not None:
            # critical-path master work is done; let the producer build
            # round t+1's context during the collect wait we enter now
            self._prefetcher.release()
        trace = self.scheduler.dispatch_round(
            t, cfg.threshold, workers=workers, monitor=self.monitor,
            timeout_s=self.round_timeout_s, payloads=payloads,
            collect_all=self.collect_all, pre_s=pre_s, post_s=post_s,
            on_result=on_result)
        if not math.isfinite(trace.t_first_R):
            # non-responders within the timeout are presumed dead
            for w in workers:
                if int(w) not in trace.arrivals:
                    self.monitor.mark_failed(int(w))
                    self._m_marked_dead.inc()
            raise ClusterDecodeError(
                f"round {t}: {len(trace.responders)} responses < threshold "
                f"{cfg.threshold} within {self.round_timeout_s}s")

        streamed = False
        alcc = self.engine_name == "alcc"
        dec_t0 = _time.perf_counter()
        if decoder is not None:
            # the streaming path never needs the batch decode matrix on a
            # hit — the decoder's accumulator IS the decode, and on a miss
            # finish() resolves its own (cached) matrix inside the timed
            # window below, so the fallback solve is attributed honestly
            order = np.asarray(trace.responders[: cfg.threshold],
                               dtype=np.int32)
        elif alcc:
            # float engine: the least-squares decode picks its own row
            # count (the ill-conditioned fallback reads ALL responders)
            _, order, _ = self.eng.survivor_round_info(cfg, trace.responders)
        else:
            with phase("decode_matrix"):
                dmat, order = engine.survivor_round(cfg, trace.responders)
        if self.distributed:
            if decoder is not None:
                # the shares are already folded (or retained) — finish is
                # one fold on a prediction hit, a batch decode on a miss
                parts = decoder.finish(order)
                streamed = decoder.streamed
                self.w2 = self._update_parts(self.w2, parts, bidx)
            elif alcc:
                fastest = np.stack([np.asarray(trace.payloads[int(w)],
                                               dtype=np.float32)
                                    for w in order])
                self.w2 = self._update(self.w2, fastest, order, bidx)
            else:
                # decode from the payloads the responders actually sent
                fastest = np.stack([np.asarray(trace.payloads[int(w)],
                                               dtype=np.int32)
                                    for w in order])
                self.w2 = self._update(self.w2, jnp.asarray(fastest),
                                       jnp.asarray(dmat, jnp.int32), bidx)
            self.w2.block_until_ready()   # honest decode_s measurement
        else:
            with phase("round_program"):
                if ctx is not None:
                    self.w2 = self._round_split(
                        ctx.kq, ctx.mask_shares, self.w2,
                        jnp.asarray(dmat, jnp.int32),
                        jnp.asarray(order, jnp.int32), bidx)
                elif alcc:
                    self.w2 = self._round(key_t, self.w2, order, bidx)
                else:
                    self.w2 = self._round(key_t, self.w2,
                                          jnp.asarray(dmat, jnp.int32),
                                          jnp.asarray(order, jnp.int32),
                                          bidx)
        decode_wall_s = _time.perf_counter() - dec_t0
        if self.distributed:
            # real transport: the scheduler cannot see master-side encode/
            # decode walls — record the measured components on the trace
            trace.encode_s = encode_wall_s
            trace.decode_s = decode_wall_s
            trace.t_ready = self.scheduler.clock
        else:
            # simulation: was this round a streaming hit?  A real decoder
            # folds eagerly only when the observed threshold subset matches
            # the prediction — on a miss it pays the full batch decode, so
            # charge the remaining decode cost to the clock (the optimistic
            # 1/threshold fold was charged inside dispatch_round)
            streamed = (self.streaming and pred_subset is not None
                        and frozenset(int(w) for w in order) == pred_subset)
            if self.streaming and not streamed:
                miss_extra = self.decode_cost_s - post_s
                if miss_extra > 0:
                    self.scheduler.time.advance_to(
                        self.scheduler.clock + miss_extra)
                    trace.decode_s = post_s + miss_extra
                    trace.t_ready = self.scheduler.clock
        self._last_order = np.asarray(trace.responders).copy()
        self.traces[t] = trace
        self.records[t] = RoundRecord(
            round=t, trace=trace, survivors=order.copy(), replayed=replayed,
            prefetched=ctx is not None, streamed=streamed)
        return trace

    # ------------------------------------------------------------------
    # Training drivers
    # ------------------------------------------------------------------

    def run(self, iters: int):
        """Plain run: any starved round raises ClusterDecodeError."""
        self._reset()
        with self._pipeline_scope(iters):
            for t in range(iters):
                self.step_round(t, iters)
        return self.eng._w_public(self.cfg, self.w2)

    def run_resilient(self, iters: int, ckpt_manager,
                      checkpoint_every: int = 5, max_retries: int = 3,
                      respawn: Callable[[int, int], None] | None = None):
        """Checkpointed run: a starved round restores the last checkpoint,
        reprovisions dead workers, and replays.

        ``respawn(worker, step)`` is the real-transport replacement hook:
        called for each dead slot after a restore, it must start a fresh
        worker process for that slot (the caller owns process management);
        the runner then reprovisions the slot over the wire and waits for
        its ack before replaying.  In simulation the latency model's
        ``revive`` plays the same role and ``respawn`` is unused.
        """
        self._reset()
        replaying = {"flag": False}

        def step_fn(state, t):
            self.w2 = jnp.asarray(state["train"]["w2"])
            self.step_round(t, iters, replayed=replaying["flag"])
            return {"train": {"w2": np.asarray(self.w2)}}

        def on_restore(step):
            replaying["flag"] = True
            t0 = self.scheduler.clock
            for i, ws in list(self.monitor.workers.items()):
                if not ws.alive:
                    if self.latency is not None:
                        self.latency.revive(i, at_round=step)
                    elif respawn is not None:
                        # real transport: spawn a fresh process for the dead
                        # slot, re-ship its share, and only revive the slot
                        # once the new process acked provisioning
                        respawn(i, step)
                        self.provision(workers=[i], timeout_s=60.0)
                    self.monitor.revive(i, now=self.scheduler.clock)
            # respawn + reprovision blocked dispatch; credit the healthy
            # fleet the stall so the replay's first fence doesn't read
            # their barrier-long silence as death
            self.monitor.credit_stall(self.scheduler.clock - t0,
                                      now=self.scheduler.clock)

        loop = ResilientLoop(ckpt_manager, checkpoint_every=checkpoint_every,
                             max_retries=max_retries, on_restore=on_restore)
        state0 = {"train": {"w2": np.asarray(self.w2)}}
        ckpt_manager.save(0, state0)
        ckpt_manager.wait()
        with self._pipeline_scope(iters):
            # a restore rewinds t; RoundPrefetcher.get resets its producer,
            # and contexts are pure functions of (kloop, t), so the replay
            # re-derives identical masks/batches
            loop.run(state0, step_fn, start_step=0, num_steps=iters)
        self.restarts = loop.restarts
        return self.eng._w_public(self.cfg, self.w2)

    def _reset(self):
        self.w2 = self.eng._w_internal(self.cfg, self.state.w)
        self.records.clear()
        self.traces.clear()
        self._last_order = None
        if self.alcc_info is not None:
            self.alcc_info.clear()

    # ------------------------------------------------------------------
    # Trace export + stats
    # ------------------------------------------------------------------

    def survivor_fn(self) -> Callable[[int], np.ndarray]:
        """Responder trace -> survivor_fn for engine.train/train_reference.

        Replaying it through the static-schedule drivers reproduces this
        run's weights bit-for-bit (the decode order fed to round_fn is
        identical).
        """
        trace = {t: rec.survivors for t, rec in self.records.items()}
        return lambda t: trace[t]

    def wait_stats(self) -> dict[str, dict[str, float]]:
        """Per-round completion-time stats: coded first-T vs wait-for-all,
        plus the master-side encode/decode components and the critical path
        (encode + wait + decode) the pipeline modes shrink."""
        recs = sorted(self.records.values(), key=lambda r: r.round)
        coded = np.array([r.coded_wait_s for r in recs])
        allw = np.array([r.all_wait_s for r in recs])
        enc = np.array([r.encode_s for r in recs])
        dec = np.array([r.decode_s for r in recs])
        stats = {"coded_T": wait_summary(coded),
                 "wait_all": wait_summary(allw[np.isfinite(allw)]),
                 "encode": wait_summary(enc),
                 "decode": wait_summary(dec),
                 "critical_path": wait_summary(enc + coded + dec),
                 # per-round bytes/frames on the wire (socket backend; all
                 # zero on the simulation, where nothing is serialized)
                 "wire_tx_bytes": wait_summary([r.tx_bytes for r in recs]),
                 "wire_rx_bytes": wait_summary([r.rx_bytes for r in recs]),
                 "wire_tx_frames": wait_summary([r.tx_frames for r in recs]),
                 "wire_rx_frames": wait_summary([r.rx_frames for r in recs]),
                 "rounds": {"n": float(len(recs)),
                            "dead_rounds": float(np.sum(~np.isfinite(allw))),
                            "prefetched": float(sum(r.prefetched
                                                    for r in recs)),
                            "streamed": float(sum(r.streamed
                                                  for r in recs))}}
        wire_totals = getattr(self.scheduler.transport, "wire_totals", None)
        if wire_totals is not None:
            # run-level totals include provisioning (the big x_share ship)
            # and heartbeats that landed between rounds
            stats["wire_totals"] = {k: float(v)
                                    for k, v in wire_totals().items()}
        # elastic membership summary: epoch 0 / joins 0 / leaves 0 on a
        # fixed-membership run
        trans = self.membership.transitions
        stats["membership"] = {
            "epoch": float(self.membership.epoch),
            "members": float(len(self.membership)),
            "spares_left": float(len(self.membership.spares)),
            "joins": float(sum(tr.kind == "join" for tr in trans)),
            "leaves": float(sum(tr.kind == "leave" for tr in trans)),
        }
        if self.master_group is not None:
            stats["masters"] = self.master_group.group_stats()
        if self.alcc_info:
            # analog-decode health: conditioning of the per-round solve and
            # the a-priori float error bound (cond * eps32 * max|eval|) —
            # the quantities DESIGN.md §14's tolerance argument rests on
            stats["alcc"] = {
                "cond": wait_summary([i["cond"] for i in self.alcc_info]),
                "abs_err_budget": wait_summary(
                    [i["abs_err_budget"] for i in self.alcc_info]),
                "fallbacks": {"n": float(sum(
                    1 for i in self.alcc_info if i["fallback"]))},
            }
        return stats
