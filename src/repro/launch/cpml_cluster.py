"""Coded cluster driver: simulated OR real multi-process deployment.

    python -m repro.launch.cpml_cluster --latency lognormal --iters 25
    python -m repro.launch.cpml_cluster --latency dead --resilient
    python -m repro.launch.cpml_cluster --pipeline full \\
        --encode-cost-s 0.2 --decode-cost-s 0.1
    python -m repro.launch.cpml_cluster --transport socket --iters 10
    python -m repro.launch.cpml_cluster --transport socket --pipeline full
    python -m repro.launch.cpml_cluster --transport socket --kill-worker 5 \\
        --kill-at-round 4
    python -m repro.launch.cpml_cluster --transport socket --masters 2 \\
        --spares 1 --kill-worker 2 --kill-at-round 3 \\
        --heartbeat-timeout 3 --join-at-round 5
    python -m repro.launch.cpml_cluster --transport socket --resilient \\
        --kill-worker 0 --kill-at-round 4
    python -m repro.launch.cpml_cluster --protocol mpc --latency lognormal
    python -m repro.launch.cpml_cluster --protocol mpc --transport socket \\
        --workers 5 --privacy 2 --straggle-worker 4
    python -m repro.launch.cpml_cluster --transport socket --straggle-worker 3 \\
        --trace-out run.trace.json --metrics-out metrics.prom

Runs CodedPrivateML training through the cluster runtime (repro.cluster):
per-round dispatch to N workers, decode at the fastest-`threshold`
responders, and a report of what the wait-for-fastest-T policy saved over
wait-for-all — the paper's headline systems effect, measured per round.

``--transport inprocess`` (default) is the event-driven simulation under a
chosen ``--latency`` profile; ``--resilient`` adds checkpoint/restore
recovery for mid-run worker death (pair with ``--latency dead``).

``--transport socket`` spawns N REAL worker processes on localhost, ships
coded shares as wire frames over TCP, and decodes from the bytes the
fastest responders actually sent — then verifies the weights are
bit-identical to ``train_reference`` replaying the observed responder trace
(DESIGN.md §7: the runtime layer changes when and where rounds execute,
never what they compute).  ``--kill-worker`` crashes one worker mid-run to
demo first-T decode riding through a real death.

``--spares``, ``--join-at-round`` and ``--masters`` exercise the elastic
membership + sharded-master plane (DESIGN.md §13): spare Lagrange
evaluation points absorb mid-run JOINs and permanent LEAVE replacements
without re-encoding the dataset, and a master group of S shards the
per-round encode + streaming decode over contiguous d-slices.  Every
variant stays bit-identical to ``train_reference`` over the observed
responder trace.

``--protocol mpc`` runs the BGW baseline head-to-head over the SAME
runtime: r+1 all-to-all reshare barriers per iteration (workers exchange
SubShares through the master's relay on the socket backend), reconstruction
at the first 2T+1 final shares, and an end-of-run bit-identity check
against the single-host ``mpc_baseline`` oracle.  A straggler stalls every
round (no erasures in BGW) — compare its per-round waits with a coded run
under the same latency profile to see the paper's Fig. 5 effect measured.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import subprocess
import sys
import tempfile
import time

# Documented ALCC verification tolerances (DESIGN.md §14).  A socket run
# replays through train_reference to within ALCC_SOCKET_TOL in max|Δw|
# (XLA-vs-BLAS float32 summation order; sim replays are bit-exact and do
# not use this).  An MLP training run must land within ALCC_MLP_LOSS_TOL
# of the plaintext jax.grad oracle's final full-data loss.
ALCC_SOCKET_TOL = 1e-3
ALCC_MLP_LOSS_TOL = 0.05


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="CodedPrivateML cluster driver")
    ap.add_argument("--protocol", choices=("cpml", "mpc"), default="cpml",
                    help="cpml = coded training (first-T decode); mpc = the "
                         "BGW baseline run as a real distributed protocol "
                         "over the same runtime (wait-for-all reshare "
                         "barriers, reconstruct at the first 2T+1)")
    ap.add_argument("--engine", choices=("exact", "alcc"), default="exact",
                    help="coded-arithmetic backend (DESIGN.md §14): exact = "
                         "quantized Lagrange coding over F_p with "
                         "bit-identical decode; alcc = real-valued Lagrange "
                         "coding with Gaussian analog masks and a "
                         "least-squares decode whose condition number / "
                         "error budget are tracked per round")
    ap.add_argument("--model", choices=("logreg", "mlp"), default="logreg",
                    help="logreg = the paper's logistic regression; mlp = "
                         "the two-layer gelu MLP (models/layers.py) trained "
                         "as two bilinear coded phases per step — ALCC "
                         "engine only (gelu/softmax are not field "
                         "polynomials)")
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="ALCC Gaussian mask std — the analog privacy knob; "
                         "its cost is proportional decode roundoff "
                         "(--engine alcc only)")
    ap.add_argument("--hidden", type=int, default=32,
                    help="MLP hidden width (--model mlp)")
    ap.add_argument("--eta", type=float, default=0.1,
                    help="MLP step size for both layers (--model mlp; "
                         "logreg keeps the Lipschitz auto-tuned step)")
    ap.add_argument("--workers", "-N", type=int, default=8)
    ap.add_argument("--parallel", "-K", type=int, default=2)
    ap.add_argument("--privacy", "-T", type=int, default=1)
    ap.add_argument("--degree", "-r", type=int, default=1)
    ap.add_argument("--classes", "-c", type=int, default=1)
    ap.add_argument("--m", type=int, default=2000, help="samples")
    ap.add_argument("--d", type=int, default=128, help="features")
    ap.add_argument("--iters", type=int, default=25)
    ap.add_argument("--batch-rows", type=int, default=None)
    ap.add_argument("--transport", choices=("inprocess", "socket"),
                    default="inprocess",
                    help="inprocess = event-driven simulation; socket = "
                         "spawn N real worker processes on localhost")
    ap.add_argument("--pipeline", choices=("off", "prefetch", "streaming",
                                           "full"),
                    default="off",
                    help="overlap master-side coding with in-flight worker "
                         "compute (DESIGN.md §9): prefetch = next round's "
                         "masks/batch/decode-coefficients built during the "
                         "wait; streaming = fold shares into the decode as "
                         "they arrive; full = both.  Bit-identical to off "
                         "in every mode")
    ap.add_argument("--encode-cost-s", type=float, default=0.0,
                    help="modeled master encode seconds per round charged "
                         "to the simulated clock (inprocess only; shows "
                         "the pipelining win on the sim timeline)")
    ap.add_argument("--decode-cost-s", type=float, default=0.0,
                    help="modeled master decode seconds per round "
                         "(inprocess only)")
    ap.add_argument("--latency", choices=("deterministic", "lognormal",
                                          "bursty", "dead"),
                    default="lognormal",
                    help="per-worker latency profile (inprocess only)")
    ap.add_argument("--latency-seed", type=int, default=0)
    ap.add_argument("--round-timeout", type=float, default=math.inf,
                    help="seconds before a round is declared starved "
                         "(required for --latency dead; defaults to 120 "
                         "wall seconds for --transport socket)")
    ap.add_argument("--resilient", action="store_true",
                    help="checkpoint/restore recovery on starved rounds "
                         "(socket: a respawned replacement process is "
                         "reprovisioned over the wire before the replay)")
    ap.add_argument("--checkpoint-every", type=int, default=5)
    # elastic membership + sharded masters (DESIGN.md §13)
    ap.add_argument("--masters", type=int, default=1,
                    help="shard the master role over this many d-slices "
                         "(DESIGN.md §13): each master of the group encodes "
                         "and stream-decodes a contiguous 1/S slice of the "
                         "model dimension — bit-identical to one master, "
                         "1/S the per-master critical path at large d")
    ap.add_argument("--spares", type=int, default=0,
                    help="pre-encode this many spare Lagrange evaluation "
                         "points beyond N (DESIGN.md §13): the alphas are "
                         "consecutive, so shares 0..N-1 are unchanged and "
                         "spare slots absorb elastic JOINs without ever "
                         "re-encoding the dataset")
    ap.add_argument("--join-at-round", type=int, default=None,
                    help="elastic JOIN demo: admit one extra worker at this "
                         "round's fence (socket: spawns a real late-joiner "
                         "process that announces itself with a JOIN frame; "
                         "inprocess: a scheduled join); implies --spares 1")
    # socket-transport options
    ap.add_argument("--port", type=int, default=0,
                    help="master TCP port (0 = ephemeral)")
    ap.add_argument("--kill-worker", type=int, default=None,
                    help="crash this worker index mid-run (socket only)")
    ap.add_argument("--kill-at-round", type=int, default=4,
                    help="round at which --kill-worker crashes")
    ap.add_argument("--straggle-worker", type=int, default=None,
                    help="make this worker sleep before every reply "
                         "(socket only)")
    ap.add_argument("--straggle-sleep", type=float, default=0.25)
    ap.add_argument("--collect-all", action="store_true",
                    help="keep each round open until every dispatched "
                         "worker responds, so the wait-for-all "
                         "counterfactual is measured on the real clock "
                         "(socket only; do not combine with --kill-worker)")
    ap.add_argument("--heartbeat-timeout", type=float, default=math.inf,
                    help="wall seconds of heartbeat silence before a worker "
                         "drops from the dispatch set (socket only)")
    ap.add_argument("--wire", choices=("v1", "v2"), default="v2",
                    help="wire protocol version for the socket transport "
                         "(DESIGN.md §10): v2 = bit-packed, coalesced, "
                         "scatter-gather frames negotiated at HELLO; v1 = "
                         "force the legacy format end to end (master AND "
                         "spawned workers) for byte-for-byte comparison")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the bit-identity check vs train_reference "
                         "(socket only)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json-out", type=str, default=None)
    # flight recorder (DESIGN.md §11) — off unless asked for: the recorder
    # costs nothing when absent (NullRecorder no-ops on every hot-path site)
    ap.add_argument("--trace-out", type=str, default=None,
                    help="record a flight trace and write Perfetto/Chrome "
                         "trace-event JSON here (load at ui.perfetto.dev or "
                         "chrome://tracing); also prints a terminal "
                         "waterfall + straggler attribution post-run")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the run's metrics registry here: a *.json "
                         "path gets the JSON snapshot, anything else the "
                         "Prometheus textfile format")
    return ap


def _worker_env() -> dict[str, str]:
    """Environment for a spawned cpml_worker: this tree on PYTHONPATH,
    CPU-pinned jax."""
    src_root = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def spawn_worker(port: int, w: int, *, env: dict[str, str] | None = None,
                 wire_version: int = 2, die_at_round: int | None = None,
                 sleep_s: float | None = None,
                 join_at_round: int | None = None) -> subprocess.Popen:
    """Start one cpml_worker process for slot ``w`` against the master
    listening on ``port``.  Also the resilient-restore respawn primitive:
    a replacement for a dead slot is spawned exactly like the original."""
    cmd = [sys.executable, "-m", "repro.launch.cpml_worker",
           "--host", "127.0.0.1", "--port", str(port),
           "--worker", str(w), "--wire", str(wire_version)]
    if die_at_round is not None:
        cmd += ["--die-at-round", str(die_at_round)]
    if sleep_s is not None:
        cmd += ["--sleep-s", str(sleep_s)]
    if join_at_round is not None:
        cmd += ["--join-at-round", str(join_at_round)]
    return subprocess.Popen(cmd,
                            env=env if env is not None else _worker_env())


@contextlib.contextmanager
def local_socket_cluster(n_workers: int, *, port: int = 0,
                         die_at_round: dict[int, int] | None = None,
                         sleep_s: dict[int, float] | None = None,
                         join_at_round: dict[int, int] | None = None,
                         connect_timeout_s: float = 60.0,
                         poll_interval_s: float = 0.02,
                         wire_version: int = 2):
    """Spawn N cpml_worker processes against a fresh master transport.

    Yields the master ``SocketTransport`` once every worker has connected
    and HELLOed.  On exit the worker processes are terminated and the
    transport closed.  Reused by the slow socket tests, so every consumer
    launches workers the same way.
    ``wire_version=1`` forces the legacy wire format on the master AND every
    spawned worker (the v1 baseline for byte-for-byte comparison).

    ``join_at_round={slot: round}`` additionally spawns elastic late
    joiners (DESIGN.md §13): each runs with ``--join-at-round`` and is NOT
    provisioned with the base fleet — it announces a JOIN and waits for the
    master's fence to admit it.  The yielded transport carries the spawned
    process list as ``tr.procs`` so a resilient respawn hook can append
    replacements and have the exit path reap them too.
    """
    from repro.cluster.socket_transport import SocketTransport
    from repro.cluster.messages import worker_endpoint

    env = _worker_env()
    tr = SocketTransport.master(port=port, poll_interval_s=poll_interval_s,
                                wire_version=wire_version)
    procs: list[subprocess.Popen] = []
    tr.procs = procs
    try:
        for w in range(n_workers):
            procs.append(spawn_worker(
                tr.port, w, env=env, wire_version=wire_version,
                die_at_round=(die_at_round or {}).get(w),
                sleep_s=(sleep_s or {}).get(w)))
        for w, at_round in (join_at_round or {}).items():
            procs.append(spawn_worker(tr.port, w, env=env,
                                      wire_version=wire_version,
                                      join_at_round=at_round))
        # joiners connect (and JOIN) right away too: waiting for their
        # HELLO here makes the admission round deterministic for tests —
        # admission itself still only happens at the master's fence
        expect = [*range(n_workers), *(join_at_round or {})]
        tr.wait_for_endpoints([worker_endpoint(w) for w in expect],
                              timeout_s=connect_timeout_s)
        yield tr
    finally:
        tr.close()
        deadline = time.monotonic() + 10.0
        for p in procs:
            # closing the transport hangs up on every worker, which exits
            # its serve loop; escalate only if one wedges.
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _recorder_for(args):
    """A live Recorder when --trace-out asked for one, else None (the
    runners fall back to the no-op NullRecorder)."""
    if args.trace_out is None:
        return None
    from repro.obs.trace import Recorder
    return Recorder()


def _emit_obs(args, runner, threshold: int) -> None:
    """Post-run observability outputs: Perfetto trace file, terminal
    waterfall, straggler attribution, metrics registry dump."""
    if args.trace_out:
        from repro.obs.export import (straggler_report, waterfall,
                                      write_chrome_trace)
        obj = write_chrome_trace(runner.obs, args.trace_out)
        pids = {e.get("pid") for e in obj["traceEvents"]}
        print(f"trace: {len(obj['traceEvents'])} events / {len(pids)} "
              f"process(es) -> {args.trace_out} (load at ui.perfetto.dev)")
        print(waterfall(runner.obs))
        text, _ = straggler_report(runner.traces, threshold)
        print(text)
    if args.metrics_out:
        runner.metrics.write(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")


def _validate(args) -> int | None:
    """The cross-flag refusal matrix: every structurally impossible combo
    dies here with one clear sentence on stderr and rc 2, mirroring the
    historical --pipeline-with-MPC refusal.  Returns None when the combo
    is runnable."""
    if args.engine == "alcc" and args.protocol == "mpc":
        print("--engine alcc cannot run --protocol mpc: BGW is an exact "
              "finite-field protocol (Shamir shares, modular reshare "
              "barriers) — there is no analog/float variant of its "
              "degree reduction", file=sys.stderr)
        return 2
    if args.model == "mlp":
        if args.protocol == "mpc":
            print("--model mlp is a coded-protocol feature: the BGW "
                  "baseline reproduces the paper's logistic task only",
                  file=sys.stderr)
            return 2
        if args.engine != "alcc":
            print("--model mlp needs --engine alcc: gelu and softmax are "
                  "not finite-field polynomials, so the exact engine "
                  "structurally cannot train the MLP (DESIGN.md §14)",
                  file=sys.stderr)
            return 2
        if args.resilient or args.collect_all:
            print("--resilient/--collect-all are not wired into the MLP "
                  "plane yet — drop them or use --model logreg",
                  file=sys.stderr)
            return 2
    if args.engine == "alcc":
        if args.pipeline != "off":
            print("--pipeline modes are exact-engine only: they split the "
                  "FIELD encode/decode (prefetchable mask rows, integer "
                  "streaming folds) — the ALCC least-squares decode has "
                  "no such split", file=sys.stderr)
            return 2
        if args.masters > 1 or args.spares or args.join_at_round is not None:
            print("--masters/--spares/--join-at-round are exact-engine "
                  "only: the elastic + sharded-master planes rely on "
                  "bit-identical re-encode, which a float engine cannot "
                  "promise", file=sys.stderr)
            return 2
        if args.transport == "socket" and args.wire == "v1":
            print("--engine alcc needs --wire v2: float round shares and "
                  "results are wire v2 frames (like TRACE/JOIN) — a v1 "
                  "fleet has no frame for them", file=sys.stderr)
            return 2
    return None


def _run_socket(args, cfg, key, x, y) -> tuple:
    """--transport socket: N real worker processes, wire frames, wall clock."""
    import numpy as np

    from repro.cluster import ClusterRunner
    from repro.core import protocol
    from repro.core.protocol import alcc_engine

    die = ({args.kill_worker: args.kill_at_round}
           if args.kill_worker is not None else None)
    sleep = ({args.straggle_worker: args.straggle_sleep}
             if args.straggle_worker is not None else None)
    timeout = args.round_timeout
    if math.isinf(timeout):
        timeout = 120.0         # real silence must be detectable
    wv = int(args.wire[1:])
    spares = args.spares
    join = None
    if args.join_at_round is not None:
        spares = max(spares, 1)
        join = {cfg.N: args.join_at_round}      # first spare slot
    with local_socket_cluster(cfg.N, port=args.port, die_at_round=die,
                              sleep_s=sleep, join_at_round=join,
                              wire_version=wv) as tr:
        runner = ClusterRunner(cfg, key, x, y, latency=None, transport=tr,
                               round_timeout_s=timeout,
                               heartbeat_timeout_s=args.heartbeat_timeout,
                               collect_all=args.collect_all,
                               pipeline=args.pipeline,
                               spares=spares, masters=args.masters,
                               recorder=_recorder_for(args),
                               engine=args.engine)
        runner.provision()
        t0 = time.monotonic()
        if args.resilient:
            from repro.checkpoint.manager import CheckpointManager
            from repro.cluster.messages import worker_endpoint
            env = _worker_env()

            def respawn(worker: int, step: int) -> None:
                # a starved round's restore asks for a fresh process for
                # each dead slot; the runner reprovisions it over the wire
                # and waits for its ack before replaying
                tr.procs.append(spawn_worker(tr.port, worker, env=env,
                                             wire_version=wv))
                tr.wait_for_endpoints([worker_endpoint(worker)],
                                      timeout_s=60.0)

            with tempfile.TemporaryDirectory() as ckdir:
                mgr = CheckpointManager(ckdir, async_write=False)
                w = runner.run_resilient(
                    args.iters, mgr,
                    checkpoint_every=args.checkpoint_every, respawn=respawn)
        else:
            w = runner.run(args.iters)
        wall_s = time.monotonic() - t0
        runner.shutdown_workers()
    print(f"socket run: {args.iters} rounds over TCP in {wall_s:.1f}s "
          f"({wall_s / args.iters * 1e3:.0f} ms/round)")
    if args.resilient:
        print(f"resilient socket run: {runner.restarts} restart(s), each "
              f"respawning + reprovisioning the dead slot over TCP")
    stats = runner.wait_stats()
    memb = stats["membership"]
    if memb["joins"] or memb["leaves"]:
        print(f"membership: epoch {int(memb['epoch'])}, "
              f"{int(memb['members'])} member(s) "
              f"({int(memb['joins'])} join(s), {int(memb['leaves'])} "
              f"leave(s), {int(memb['spares_left'])} spare(s) left)")
    if args.masters > 1:
        g = stats["masters"]
        print(f"sharded masters x{args.masters}: per-master critical path "
              f"{g['critical_path_s']:.3f}s (group totals: encode "
              f"{g['encode_total_s']:.3f}s, decode {g['decode_total_s']:.3f}s)")
    if "wire_totals" in stats:
        tot, per = stats["wire_totals"], stats["wire_tx_bytes"]
        print(f"wire [{args.wire}]: {tot['tx_bytes'] / 1e6:.2f} MB tx / "
              f"{tot['rx_bytes'] / 1e6:.2f} MB rx total "
              f"({per['mean'] / 1e3:.1f} kB/round tx, "
              f"{stats['wire_rx_bytes']['mean'] / 1e3:.1f} kB/round rx, "
              f"{int(tot['tx_frames'])} frames out)")
    if die:
        dead = set(die)
        late = [t for t, rec in runner.records.items()
                if dead & set(map(int, rec.survivors))]
        print(f"killed worker(s) {sorted(dead)} at round "
              f"{args.kill_at_round}: last decoded in round "
              f"{max(late) if late else '-'}; first-T decode rode through")
    if not args.no_verify:
        if args.engine == "alcc":
            # ALCC socket verification is tolerance-exact, not bit-exact:
            # the replay's BLAS einsum and the workers' XLA kernels may sum
            # float32 dot products in different orders (DESIGN.md §14's
            # documented contract — ALCC_SOCKET_TOL)
            w_ref, _ = alcc_engine.train_reference(
                runner.cfg, key, x, y, iters=args.iters,
                survivor_fn=runner.survivor_fn())
            gap = float(np.max(np.abs(np.asarray(w) - np.asarray(w_ref))))
            ok = gap <= ALCC_SOCKET_TOL
            print(f"train_reference replay over the observed responder "
                  f"trace: max|Δw| = {gap:.2e} "
                  f"(tolerance {ALCC_SOCKET_TOL:.0e}): "
                  f"{'OK' if ok else 'FAILED'}")
            if not ok:
                return runner, w, 1
        else:
            # runner.cfg is the spare-extended config when elastic (the
            # reference replays the SAME N+spares scheme over the observed
            # responder trace — bit-identity is the elastic invariant)
            w_ref, _ = protocol.train_reference(
                runner.cfg, key, x, y, iters=args.iters,
                survivor_fn=runner.survivor_fn())
            same = bool((np.asarray(w) == np.asarray(w_ref)).all())
            print(f"bit-identical to train_reference over the observed "
                  f"responder trace: {same}")
            if not same:
                return runner, w, 1
    return runner, w, 0


def _run_mpc(args) -> int:
    """--protocol mpc: the BGW baseline head-to-head on the same runtime."""
    import jax
    import numpy as np

    from repro.cluster.mpc_runner import MPCClusterRunner, mpc_phase_models
    from repro.core import mpc_baseline, protocol
    from repro.data import synthetic

    if args.resilient:
        print("--resilient is meaningless for MPC: BGW has no erasure "
              "tolerance — a starved round is terminal", file=sys.stderr)
        return 2
    if args.pipeline != "off":
        print("--pipeline applies to the coded protocol only: every BGW "
              "reshare barrier consumes the previous phase's output, so "
              "there is no W-independent master work to overlap",
              file=sys.stderr)
        return 2
    if args.classes != 1:
        print("--protocol mpc supports the paper's binary task only",
              file=sys.stderr)
        return 2
    if args.kill_worker is not None:
        print("--kill-worker is meaningless for MPC: a crashed worker "
              "starves the reshare barrier and ends the run (that is the "
              "paper's point) — use --straggle-worker to slow one instead",
              file=sys.stderr)
        return 2
    if args.masters > 1 or args.spares or args.join_at_round is not None:
        print("--masters/--spares/--join-at-round are coded-protocol "
              "features: BGW bakes N into every reshare (no spare "
              "evaluation points to join on) and its master only "
              "reconstructs", file=sys.stderr)
        return 2
    cfg = mpc_baseline.MPCConfig(N=args.workers, T=args.privacy,
                                 r=args.degree)
    mode = (args.latency if args.transport == "inprocess"
            else f"socket x{cfg.N} procs")
    print(f"BGW MPC baseline: N={cfg.N} T={cfg.T} r={cfg.r} "
          f"collect=2T+1={2 * cfg.T + 1} [{mode}] — every degree reduction "
          f"is an all-to-all barrier")
    key = jax.random.PRNGKey(args.seed)
    x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=args.m, d=args.d,
                                margin=12.0)
    rc = 0
    if args.transport == "socket":
        timeout = args.round_timeout
        if math.isinf(timeout):
            timeout = 120.0
        sleep = ({args.straggle_worker: args.straggle_sleep}
                 if args.straggle_worker is not None else None)
        with local_socket_cluster(cfg.N, port=args.port, sleep_s=sleep,
                                  wire_version=int(args.wire[1:])) as tr:
            runner = MPCClusterRunner(
                cfg, key, x, y, None, transport=tr,
                round_timeout_s=timeout,
                heartbeat_timeout_s=args.heartbeat_timeout,
                recorder=_recorder_for(args))
            runner.provision()
            t0 = time.monotonic()
            w = runner.run(args.iters)
            wall_s = time.monotonic() - t0
            runner.shutdown_workers()
        print(f"socket MPC run: {args.iters} rounds over TCP in "
              f"{wall_s:.1f}s ({wall_s / args.iters * 1e3:.0f} ms/round, "
              f"{args.degree} reshare barrier(s) each)")
    else:
        models = mpc_phase_models(args.latency, seed=args.latency_seed,
                                  r=cfg.r)
        timeout = args.round_timeout
        if args.latency == "dead" and math.isinf(timeout):
            timeout = 60.0
        runner = MPCClusterRunner(cfg, key, x, y, models,
                                  round_timeout_s=timeout,
                                  recorder=_recorder_for(args))
        w = runner.run(args.iters)
    _emit_obs(args, runner, runner.collect_threshold)
    stats = runner.wait_stats()
    word = "wall" if args.transport == "socket" else "simulated"
    print(f"per-round MPC wait (dispatch -> 2T+1 reconstruct): "
          f"mean {stats['mpc']['mean']:.2f}s  p50 {stats['mpc']['p50']:.2f}s "
          f"p95 {stats['mpc']['p95']:.2f}s "
          f"({word} total {stats['mpc']['total']:.1f}s)")
    if not args.no_verify:
        w_ref, _ = mpc_baseline.train(cfg, key, x, y, iters=args.iters)
        same = bool((np.asarray(w) == np.asarray(w_ref)).all())
        print(f"bit-identical to the single-host mpc_baseline oracle: {same}")
        if not same:
            rc = 1
    _, acc = protocol.loss_and_accuracy(w, runner.state.xq_real, y)
    print(f"accuracy: mpc {float(acc):.2%}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(_json_finite(
                {"config": {"N": cfg.N, "T": cfg.T, "r": cfg.r,
                            "protocol": "mpc",
                            "transport": args.transport,
                            "latency": (args.latency
                                        if args.transport == "inprocess"
                                        else None),
                            "iters": args.iters},
                 "wait_stats": stats,
                 "acc_mpc": float(acc)}), f, indent=2)
    return rc


def _run_mlp(args) -> int:
    """--model mlp: the two-phase coded gelu MLP under ALCC (DESIGN.md
    §14) — the model the exact engine structurally cannot train."""
    import jax
    import numpy as np

    from repro.cluster import make_latency
    from repro.cluster.alcc_mlp import ALCCMLPRunner, train_reference
    from repro.core.protocol import alcc_engine
    from repro.data import synthetic

    c = max(args.classes, 2)        # softmax head: binary becomes 2-class
    cfg = alcc_engine.ALCCConfig(N=args.workers, K=args.parallel,
                                 T=args.privacy, c=c, sigma=args.sigma,
                                 batch_rows=args.batch_rows)
    mode = (args.latency if args.transport == "inprocess"
            else f"socket x{cfg.N} procs")
    print(f"ALCC MLP cluster: N={cfg.N} K={cfg.K} T={cfg.T} c={c} "
          f"hidden={args.hidden} sigma={cfg.sigma} "
          f"phase-threshold={cfg.mlp_threshold} [{mode}]")
    key = jax.random.PRNGKey(args.seed)
    x, y = synthetic.multiclass_mnist_like(jax.random.PRNGKey(1),
                                           m=args.m, d=args.d, c=c)
    rc = 0
    if args.transport == "socket":
        timeout = args.round_timeout
        if math.isinf(timeout):
            timeout = 120.0
        sleep = ({args.straggle_worker: args.straggle_sleep}
                 if args.straggle_worker is not None else None)
        die = ({args.kill_worker: args.kill_at_round}
               if args.kill_worker is not None else None)
        with local_socket_cluster(cfg.N, port=args.port, sleep_s=sleep,
                                  die_at_round=die,
                                  wire_version=int(args.wire[1:])) as tr:
            runner = ALCCMLPRunner(cfg, key, x, y, args.hidden,
                                   latency=None, transport=tr,
                                   eta=args.eta, round_timeout_s=timeout,
                                   recorder=_recorder_for(args))
            runner.provision()
            t0 = time.monotonic()
            w1, w2 = runner.run(args.iters)
            wall_s = time.monotonic() - t0
            runner.shutdown_workers()
        print(f"socket MLP run: {args.iters} steps (2 coded phases each) "
              f"over TCP in {wall_s:.1f}s "
              f"({wall_s / args.iters * 1e3:.0f} ms/step)")
    else:
        latency = make_latency(args.latency, seed=args.latency_seed)
        runner = ALCCMLPRunner(cfg, key, x, y, args.hidden, latency,
                               eta=args.eta,
                               round_timeout_s=args.round_timeout,
                               recorder=_recorder_for(args))
        runner.run(args.iters)
        w1, w2 = runner.w1, runner.w2
    if args.trace_out or args.metrics_out:
        _emit_obs(args, runner, cfg.mlp_threshold)
    stats = runner.wait_stats()
    a = stats["alcc"]
    print(f"alcc decode: cond p95 {a['cond']['p95']:.1f}, error budget "
          f"p95 {a['abs_err_budget']['p95']:.2e}, "
          f"{int(a['fallbacks']['n'])} fallback(s)")
    coded = stats["coded_T"]
    print(f"per-phase wait  coded-T: mean {coded['mean']:.3f}s  "
          f"p50 {coded['p50']:.3f}s  p95 {coded['p95']:.3f}s")
    loss, acc = runner.metrics_now()
    ow1, ow2 = alcc_engine.mlp_oracle(cfg, key, x, y, args.hidden,
                                      args.iters, args.eta)
    oloss, oacc = alcc_engine.mlp_metrics(runner.state, ow1, ow2)
    print(f"MLP loss {loss:.4f} / acc {acc:.2%} vs plaintext jax.grad "
          f"oracle {oloss:.4f} / {oacc:.2%} "
          f"(|Δloss| = {abs(loss - oloss):.2e}, "
          f"tolerance {ALCC_MLP_LOSS_TOL})")
    if abs(loss - oloss) > ALCC_MLP_LOSS_TOL:
        rc = 1
    if not args.no_verify:
        w1r, w2r, _ = train_reference(cfg, key, x, y, args.hidden,
                                      args.iters, args.eta,
                                      survivor_fn=runner.survivor_fn())
        gap = max(float(np.max(np.abs(np.asarray(w1) - np.asarray(w1r)))),
                  float(np.max(np.abs(np.asarray(w2) - np.asarray(w2r)))))
        if args.transport == "socket":
            ok = gap <= ALCC_SOCKET_TOL
            print(f"train_reference replay: max|Δw| = {gap:.2e} "
                  f"(tolerance {ALCC_SOCKET_TOL:.0e}): "
                  f"{'OK' if ok else 'FAILED'}")
        else:
            ok = gap == 0.0
            print(f"bit-identical to train_reference over the observed "
                  f"responder trace: {ok}")
        if not ok:
            rc = 1
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(_json_finite(
                {"config": {"N": cfg.N, "K": cfg.K, "T": cfg.T, "c": c,
                            "engine": "alcc", "model": "mlp",
                            "hidden": args.hidden, "sigma": cfg.sigma,
                            "eta": args.eta,
                            "transport": args.transport,
                            "iters": args.iters},
                 "wait_stats": stats,
                 "loss_coded": float(loss), "acc_coded": float(acc),
                 "loss_oracle": float(oloss),
                 "acc_oracle": float(oacc)}), f, indent=2)
    return rc


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    rc = _validate(args)
    if rc is not None:
        return rc
    from repro.launch import device
    print(device.device_line())
    device.enable_compile_cache()

    if args.protocol == "mpc":
        return _run_mpc(args)
    if args.model == "mlp":
        return _run_mlp(args)

    import jax

    from repro.cluster import ClusterRunner, make_latency
    from repro.core import protocol
    from repro.core.protocol import alcc_engine
    from repro.data import synthetic

    if args.engine == "alcc":
        cfg = alcc_engine.ALCCConfig(N=args.workers, K=args.parallel,
                                     T=args.privacy, r=args.degree,
                                     c=args.classes, sigma=args.sigma,
                                     batch_rows=args.batch_rows)
    else:
        cfg = protocol.CPMLConfig(N=args.workers, K=args.parallel,
                                  T=args.privacy, r=args.degree,
                                  c=args.classes,
                                  batch_rows=args.batch_rows)
    mode = (args.latency if args.transport == "inprocess"
            else f"socket x{cfg.N} procs")
    print(f"CPML cluster [{args.engine}]: N={cfg.N} K={cfg.K} T={cfg.T} "
          f"r={cfg.r} c={cfg.c} threshold={cfg.threshold} [{mode}]")

    key = jax.random.PRNGKey(args.seed)
    if cfg.c == 1:
        x, y = synthetic.mnist_like(jax.random.PRNGKey(1), m=args.m,
                                    d=args.d, margin=12.0)
    else:
        x, y = synthetic.multiclass_mnist_like(jax.random.PRNGKey(1),
                                               m=args.m, d=args.d, c=cfg.c)

    rc = 0
    if args.transport == "socket":
        runner, w, rc = _run_socket(args, cfg, key, x, y)
    else:
        kw = {}
        if args.latency == "dead" and args.resilient:
            # kill one worker more than coding tolerates, so the run
            # exercises checkpoint restore + reprovision (a single death at
            # N=8 is absorbed by the first-T decode with no restart at all)
            spare = cfg.N - cfg.threshold
            kw["deaths"] = {w: 3 for w in range(spare + 1)}
        latency = make_latency(args.latency, seed=args.latency_seed, **kw)
        timeout = args.round_timeout
        if args.latency == "dead" and math.isinf(timeout):
            timeout = 60.0          # a dead worker must be detectable
        spares = args.spares
        join_schedule = None
        if args.join_at_round is not None:
            spares = max(spares, 1)
            join_schedule = {cfg.N: args.join_at_round}  # first spare slot
        runner = ClusterRunner(cfg, key, x, y, latency,
                               round_timeout_s=timeout,
                               heartbeat_timeout_s=args.heartbeat_timeout,
                               pipeline=args.pipeline,
                               encode_cost_s=args.encode_cost_s,
                               decode_cost_s=args.decode_cost_s,
                               spares=spares, masters=args.masters,
                               join_schedule=join_schedule,
                               recorder=_recorder_for(args),
                               engine=args.engine)
        if args.resilient:
            from repro.checkpoint.manager import CheckpointManager
            with tempfile.TemporaryDirectory() as ckdir:
                mgr = CheckpointManager(ckdir, async_write=False)
                w = runner.run_resilient(
                    args.iters, mgr, checkpoint_every=args.checkpoint_every)
            print(f"resilient run: {runner.restarts} restart(s)")
        else:
            w = runner.run(args.iters)

    _emit_obs(args, runner, cfg.threshold)
    stats = runner.wait_stats()
    memb = stats["membership"]
    if args.transport != "socket" and (memb["joins"] or memb["leaves"]):
        # (the socket path already printed its membership line)
        print(f"membership: epoch {int(memb['epoch'])}, "
              f"{int(memb['members'])} member(s) "
              f"({int(memb['joins'])} join(s), {int(memb['leaves'])} "
              f"leave(s), {int(memb['spares_left'])} spare(s) left)")
    coded, allw = stats["coded_T"], stats["wait_all"]
    print(f"per-round wait  coded-T: mean {coded['mean']:.2f}s  "
          f"p50 {coded['p50']:.2f}s  p95 {coded['p95']:.2f}s")
    if args.pipeline != "off" or args.encode_cost_s or args.decode_cost_s:
        cp, enc, dec = (stats["critical_path"], stats["encode"],
                        stats["decode"])
        print(f"per-round critical path [{args.pipeline}]: "
              f"mean {cp['mean']:.3f}s = encode {enc['mean']:.3f}s + wait "
              f"+ decode {dec['mean']:.3f}s  "
              f"({int(stats['rounds']['prefetched'])} prefetched, "
              f"{int(stats['rounds']['streamed'])} streamed rounds)")
    unobserved = int(stats["rounds"]["dead_rounds"])
    # an UNOBSERVED wait-for-all series is all-zero (wait_summary zeroes an
    # empty input), so gate on total > 0 rather than finiteness
    if allw["total"] > 0:
        print(f"per-round wait wait-all: mean {allw['mean']:.2f}s  "
              f"p50 {allw['p50']:.2f}s  p95 {allw['p95']:.2f}s")
    if unobserved and args.transport == "socket" and not args.collect_all:
        print(f"(wait-for-all unobserved in first-T mode: the master moved "
              f"on at the threshold-th arrival every round; rerun with "
              f"--collect-all to measure it)")
    elif unobserved:
        print(f"({unobserved} round(s) had a dead worker: wait-for-all "
              f"would NEVER complete; wait-all stats cover the "
              f"{int(stats['rounds']['n']) - unobserved} finite rounds)")
    if unobserved == 0 and allw["total"] > 0 and math.isfinite(allw["total"]):
        word = "wall" if args.transport == "socket" else "simulated"
        print(f"{word} training time: {coded['total']:.1f}s coded-T vs "
              f"{allw['total']:.1f}s wait-all "
              f"({allw['total'] / coded['total']:.2f}x speedup)")

    if args.engine == "alcc":
        import numpy as np
        a = stats["alcc"]
        print(f"alcc decode: cond p95 {a['cond']['p95']:.1f}, error budget "
              f"p95 {a['abs_err_budget']['p95']:.2e}, "
              f"{int(a['fallbacks']['n'])} fallback(s)")
        if args.transport != "socket" and not args.no_verify:
            # sim replay is bit-exact (same numpy ops on the same inputs);
            # the socket path already verified inside _run_socket
            w_ref, _ = alcc_engine.train_reference(
                cfg, key, x, y, iters=args.iters,
                survivor_fn=runner.survivor_fn())
            same = bool((np.asarray(w) == np.asarray(w_ref)).all())
            print(f"bit-identical to train_reference over the observed "
                  f"responder trace: {same}")
            if not same:
                rc = 1
        # accuracy vs the UNCODED float oracle (same surrogate, batches
        # and steps — the gap is pure coding/decoding float error)
        w_oracle = alcc_engine.float_oracle(cfg, key, x, y, args.iters)
        metric = (protocol.loss_and_accuracy if cfg.c == 1
                  else protocol.multiclass_loss_and_accuracy)
        x_eval = runner.state.xq_real[: runner.state.m]
        _, acc = metric(w, x_eval, y)
        _, acc_ref = metric(w_oracle, x_eval, y)
        print(f"accuracy: coded {float(acc):.2%} vs uncoded float oracle "
              f"{float(acc_ref):.2%}")
    else:
        # accuracy vs the cleartext quantized baseline, same step count
        wc, xq = protocol.cleartext_baseline(cfg, x, y, args.iters)
        metric = (protocol.loss_and_accuracy if cfg.c == 1
                  else protocol.multiclass_loss_and_accuracy)
        _, acc = metric(w, xq, y)
        _, acc_ref = metric(wc, xq, y)
        print(f"accuracy: coded {float(acc):.2%} vs cleartext baseline "
              f"{float(acc_ref):.2%}")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(_json_finite({"config": {"N": cfg.N, "K": cfg.K, "T": cfg.T,
                                  "r": cfg.r, "c": cfg.c,
                                  "engine": args.engine,
                                  "sigma": (args.sigma
                                            if args.engine == "alcc"
                                            else None),
                                  "masters": args.masters,
                                  "spares": args.spares,
                                  "transport": args.transport,
                                  "latency": (args.latency
                                              if args.transport == "inprocess"
                                              else None),
                                  "iters": args.iters},
                       "wait_stats": stats,
                       "restarts": getattr(runner, "restarts", 0),
                       "acc_coded": float(acc),
                       "acc_baseline": float(acc_ref)}), f, indent=2)
    return rc


def _json_finite(obj):
    """inf/nan -> null recursively: json.dump would emit bare ``Infinity``
    tokens (rejected by strict RFC-8259 parsers), and unobserved wait-all
    stats are legitimately inf on a first-T socket run."""
    if isinstance(obj, dict):
        return {k: _json_finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


if __name__ == "__main__":
    sys.exit(main())
