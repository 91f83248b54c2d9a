"""CodedPrivateML worker process: serve coded OR MPC rounds over a socket.

    python -m repro.launch.cpml_worker --host 127.0.0.1 --port 9000 --worker 3

One process = one of the paper's N workers.  It connects to the master's
SocketTransport, registers its endpoint ("worker/3"), and serves the
message protocol (DESIGN.md §7):

  1. PROVISION — an EncodeShare with ``round == PROVISION_ROUND`` carrying
     {cfg kwargs, the worker's coded dataset share X̃_i, sigmoid-surrogate
     coefficients c̄}.  A ``"protocol": "mpc"`` key selects the BGW serve
     mode (the share is then a FULL-dataset Shamir share); an
     ``"protocol": "alcc"`` key selects the ALCC float backend (DESIGN.md
     §14) — the share is a real-valued float32 Lagrange share and the
     round function is the float surrogate evaluation X̃ᵀĝ(X̃W̃); an
     ``"protocol": "alcc_mlp"`` key serves the two-phase coded MLP
     (cluster/alcc_mlp.py) — even rounds compute the coded forward
     X̃·W̃1, odd rounds the coded backward X̃[batch]ᵀ·δ̃1; a
     ``"protocol": "serve"`` key selects the prediction-serving plane
     (cluster/serve.py) — the payload carries the model share W̃_i held
     for the deployment's lifetime, and each later round ships a query
     share X̃_i answered with the bilinear evaluation X̃_i·W̃_i.  The
     worker acks with a Heartbeat once loaded.
  2. ROUNDS    — CPML: each EncodeShare(t, i, {"w_share", "batch"}) is
     acked with an immediate Heartbeat (liveness), then answered with
     WorkerResult(t, i, compute_s, payload=f(X̃_i, W̃_i)).  A pipelined
     master (DESIGN.md §9) additionally ships "next_batch" — round t+1's
     W-independent batch indices — and the worker pre-slices that coded
     sub-batch after replying, while its next weight share is in flight.  MPC: the share
     carries {"w_share", "kred"}; the worker runs the BGW phases — local
     multiply, then one all-to-all reshare BARRIER per degree reduction
     (SubShares exchanged with every peer through the master's relay;
     combining needs ALL N, so one slow peer stalls this worker too) —
     and answers with CombineResult(t, i, compute_s, payload=g-share).
     All field math is exact int32 mod p via the same core/mpc_baseline
     hooks the single-host oracle composes, so the master's reconstruction
     is bit-identical to computing the round locally.
  3. SHUTDOWN  — ``round == SHUTDOWN_ROUND`` (or the master hanging up)
     ends the serve loop.

Fault-injection flags make the failure paths deterministic for tests and
benchmarks: ``--die-at-round R`` simulates a crash (exit without replying
when round R's share arrives); ``--sleep-s S`` makes this worker a real
straggler (sleeps S seconds before every reply — in MPC mode before every
phase's sends, which stalls EVERY peer at the barrier).
"""
from __future__ import annotations

import argparse
import collections
import math
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="CodedPrivateML socket worker")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--worker", type=int, required=True,
                    help="this worker's index i in [0, N)")
    ap.add_argument("--connect-timeout", type=float, default=30.0)
    ap.add_argument("--barrier-timeout", type=float, default=600.0,
                    help="seconds to wait at an MPC reshare barrier before "
                         "giving up (a missing peer means the round can "
                         "never complete)")
    ap.add_argument("--die-at-round", type=int, default=None,
                    help="crash (exit silently) when this round's share "
                         "arrives — deterministic kill-a-worker injection")
    ap.add_argument("--join-at-round", type=int, default=None,
                    help="elastic JOIN (DESIGN.md §13): announce this "
                         "worker as a late joiner for the given round fence "
                         "right after HELLO; the master provisions its "
                         "pre-encoded spare share and admits it at the "
                         "first fence with t >= this round (wire v2 only)")
    ap.add_argument("--sleep-s", type=float, default=0.0,
                    help="sleep this long before every reply — a real "
                         "injected straggler")
    ap.add_argument("--wire", type=int, choices=(1, 2), default=2,
                    help="wire protocol version this worker speaks "
                         "(DESIGN.md §10): 2 = packed/coalesced frames "
                         "negotiated at HELLO, 1 = behave exactly like a "
                         "legacy v1 build")
    return ap


def serve(args) -> int:
    # imports deferred so --help/arg errors don't pay jax startup
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cluster.messages import (
        MASTER, PROVISION_ROUND, SHUTDOWN_ROUND, CombineResult, EncodeShare,
        Epoch, Heartbeat, Join, SubShare, WorkerResult, worker_endpoint)
    from repro.cluster.socket_transport import SocketTransport
    from repro.core import field, mpc_baseline as mpc
    from repro.core.protocol import compute
    from repro.core.protocol.config import CPMLConfig

    me = worker_endpoint(args.worker)
    tr = SocketTransport.connect(args.host, args.port, me,
                                 timeout_s=args.connect_timeout,
                                 wire_version=args.wire)
    if args.join_at_round is not None:
        if args.wire < 2:
            raise SystemExit(
                f"{me}: --join-at-round needs wire v2 (a v1 fleet has no "
                f"JOIN frame)")
        # the negotiated version toward the master stays v1 until its
        # HELLO2 ack lands — wait for the upgrade, or the JOIN frame (v2
        # only) would be refused at serialization
        deadline = time.monotonic() + args.connect_timeout
        while tr.peer_version(MASTER) < 2:
            if time.monotonic() > deadline:
                raise SystemExit(
                    f"{me}: master never acked HELLO2 — cannot announce "
                    f"an elastic JOIN to a v1 master")
            tr.next_delivery(me)
        # late joiner: announce the slot + target fence; the master stashes
        # the request and answers with this worker's PROVISION at the fence
        tr.send(MASTER, Join(args.worker, args.join_at_round,
                             time.monotonic()))
    pending: collections.deque = collections.deque()
    subshares: dict[tuple[int, int], dict[int, object]] = {}
    state: dict[str, object] = {"protocol": None}

    def drain() -> None:
        """Pull everything off the wire: SubShares into the reshare buffer,
        EncodeShares into the pending work queue (with their local arrival
        stamp, so a traced round's "recv" span covers wire + queue wait)."""
        for at, msg in tr.recv(me, math.inf):
            if isinstance(msg, SubShare):
                subshares.setdefault((msg.round, msg.phase),
                                     {})[msg.src] = msg.payload
            elif isinstance(msg, Epoch):
                # informational membership fan-out: remember the fleet
                # generation (the master's round math never depends on this
                # worker having seen it)
                state["epoch"] = msg.epoch
            elif isinstance(msg, EncodeShare):
                pending.append((at, msg))

    def reshare_barrier(cfg, t: int, phase: int, kphase, value):
        """One BGW degree reduction from this worker's seat: re-share,
        send a sub-share to every peer, then BLOCK until all N sub-shares
        for (t, phase) are in and combine."""
        if args.sleep_s > 0:
            time.sleep(args.sleep_s)
        sub = np.asarray(mpc.make_subshares(
            cfg, mpc.reshare_keys(cfg, kphase)[args.worker], value),
            np.int32)                                   # (N, *value.shape)
        for v in range(cfg.N):
            if v != args.worker:
                tr.send(worker_endpoint(v),
                        SubShare(t, phase, args.worker, v, sub[v]))
        got = {args.worker: sub[args.worker]}
        deadline = time.monotonic() + args.barrier_timeout
        while len(got) < cfg.N:
            for src, payload in subshares.pop((t, phase), {}).items():
                got[src] = np.asarray(payload, np.int32)
            if len(got) == cfg.N:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{me}: reshare barrier (round {t}, phase {phase}) "
                    f"starved: peers {sorted(set(range(cfg.N)) - set(got))} "
                    f"never re-shared")
            if tr.next_delivery(me) is not None:
                drain()
        gathered = jnp.asarray(np.stack([got[i] for i in range(cfg.N)]),
                               jnp.int32)
        return mpc.combine_subshares(cfg, gathered)

    def mpc_round(at: float, msg) -> None:
        cfg, x_share, cbar = state["cfg"], state["x_share"], state["cbar"]
        t = msg.round
        t0 = time.monotonic()
        # worker-side flight-recorder spans (DESIGN.md §11): [name, start,
        # end] triples on THIS process's monotonic clock, piggy-backed on
        # the CombineResult over a v2 wire.  Each reshare barrier becomes
        # its own span, so the trace shows which phase a stall happened in.
        spans = state.pop("carry", []) if state.get("trace") else None
        if spans is not None:
            spans.append(["recv", at, t0])
        w_share = jnp.asarray(msg.payload["w_share"], jnp.int32)  # (d, r)
        kred = np.asarray(msg.payload["kred"])                    # (r+1, 2)
        z = mpc.worker_mul(cfg, x_share, w_share)                 # (m, r)
        t1 = time.monotonic()
        if spans is not None:
            spans.append(["compute", t0, t1])
        z = reshare_barrier(cfg, t, 0, jnp.asarray(kred[0]), z)
        if spans is not None:
            spans.append(["barrier", t1, time.monotonic()])
        prod = z[..., 0]
        s = mpc.s_init(cfg, cbar, prod)
        for i in range(2, cfg.r + 1):
            prod = field.mulmod(prod, z[..., i - 1], cfg.p)
            b0 = time.monotonic()
            prod = reshare_barrier(cfg, t, i - 1, jnp.asarray(kred[i - 1]),
                                   prod)
            if spans is not None:
                spans.append(["barrier", b0, time.monotonic()])
            s = mpc.s_accum(cfg, cbar[i], s, prod)
        if args.sleep_s > 0:
            time.sleep(args.sleep_s)
        t2 = time.monotonic()
        g = np.asarray(mpc.worker_final(cfg, x_share, s), np.int32)
        t3 = time.monotonic()
        if spans is not None:
            spans.append(["serialize", t2, t3])
        tr.send(MASTER, CombineResult(t, args.worker,
                                      time.monotonic() - t0, g,
                                      trace=spans))
        if spans is not None:
            # the socket write can only be timed AFTER the message is built;
            # it rides the NEXT traced round (one-round lag, like the
            # provisioning warm-compile span)
            state["carry"] = [["send", t3, time.monotonic()]]
        # reshare traffic for finished rounds can never be consumed again
        for key in [k for k in subshares if k[0] <= t]:
            del subshares[key]

    def cpml_round(at: float, msg) -> None:
        t0 = time.monotonic()
        # spans: [name, start, end] on this process's clock, shipped with
        # the result over a v2 wire (DESIGN.md §11).  "recv" covers wire +
        # queue wait (arrival stamp -> processing start); an injected
        # straggler sleep gets its own honest span.
        spans = state.pop("carry", []) if state.get("trace") else None
        if spans is not None:
            spans.append(["recv", at, t0])
        if args.sleep_s > 0:
            time.sleep(args.sleep_s)
            if spans is not None:
                spans.append(["straggle", t0, time.monotonic()])
        t1 = time.monotonic()
        w_share = jnp.asarray(msg.payload["w_share"], jnp.int32)
        batch = msg.payload.get("batch")
        x_share = state["x_share"]
        cached = state.get("xb_cache")
        if batch is None:
            xb = x_share
        elif cached is not None and cached[0] == msg.round:
            # pre-sliced from last round's "next_batch" (pipelined master,
            # DESIGN.md §9) — same indices, so the result is bit-identical
            xb = cached[1]
        else:
            xb = jnp.take(x_share, jnp.asarray(batch, jnp.int32), axis=0)
        r = state["f"](xb, w_share)
        r.block_until_ready()
        t2 = time.monotonic()
        if spans is not None:
            spans.append(["compute", t1, t2])
        result = np.asarray(r, dtype=np.int32)
        t3 = time.monotonic()
        if spans is not None:
            spans.append(["serialize", t2, t3])   # device->host materialize
        tr.send(MASTER,
                WorkerResult(msg.round, args.worker,
                             compute_s=time.monotonic() - t0,
                             payload=result, trace=spans))
        if spans is not None:
            # socket-write wall is only known after the message is built; it
            # rides the NEXT traced round, like the warm-compile span
            state["carry"] = [["send", t3, time.monotonic()]]
        nxt = msg.payload.get("next_batch")
        if nxt is not None:
            # W-independent worker-side prefetch: slice round t+1's coded
            # sub-batch AFTER replying, while waiting for its weight share
            state["xb_cache"] = (
                msg.round + 1,
                jnp.take(x_share, jnp.asarray(nxt, jnp.int32), axis=0))
        else:
            state["xb_cache"] = None

    def alcc_round(at: float, msg) -> None:
        """One ALCC float round (DESIGN.md §14): same span shape as
        cpml_round, float32 arithmetic throughout.  Logistic mode applies
        the provisioned worker polynomial; MLP mode selects the phase by
        round PARITY — even rounds are the coded forward X̃_i @ W̃1_i,
        odd rounds the coded backward X̃_i[batch]ᵀ @ δ̃1_i (both shares
        arrive under the same "w_share" key)."""
        t0 = time.monotonic()
        spans = state.pop("carry", []) if state.get("trace") else None
        if spans is not None:
            spans.append(["recv", at, t0])
        if args.sleep_s > 0:
            time.sleep(args.sleep_s)
            if spans is not None:
                spans.append(["straggle", t0, time.monotonic()])
        t1 = time.monotonic()
        w_share = jnp.asarray(msg.payload["w_share"], jnp.float32)
        batch = msg.payload.get("batch")
        x_share = state["x_share"]
        xb = (x_share if batch is None
              else jnp.take(x_share, jnp.asarray(batch, jnp.int32), axis=0))
        if state["protocol"] == "alcc_mlp":
            f = state["f_fwd"] if msg.round % 2 == 0 else state["f_bwd"]
        else:
            f = state["f"]
        r = f(xb, w_share)
        r.block_until_ready()
        t2 = time.monotonic()
        if spans is not None:
            spans.append(["compute", t1, t2])
        result = np.asarray(r, dtype=np.float32)
        t3 = time.monotonic()
        if spans is not None:
            spans.append(["serialize", t2, t3])
        tr.send(MASTER,
                WorkerResult(msg.round, args.worker,
                             compute_s=time.monotonic() - t0,
                             payload=result, trace=spans))
        if spans is not None:
            state["carry"] = [["send", t3, time.monotonic()]]

    def serve_round(at: float, msg) -> None:
        """One coded prediction flush (cluster/serve.py): a query share
        X̃_i arrives, reply with the bilinear evaluation X̃_i·W̃_i.  Same
        span shape as cpml_round so the master's per-query waterfall and
        the training waterfall read identically."""
        t0 = time.monotonic()
        spans = state.pop("carry", []) if state.get("trace") else None
        if spans is not None:
            spans.append(["recv", at, t0])
        if args.sleep_s > 0:
            time.sleep(args.sleep_s)
            if spans is not None:
                spans.append(["straggle", t0, time.monotonic()])
        t1 = time.monotonic()
        xb = jnp.asarray(msg.payload["x_share"], jnp.int32)
        r = state["f"](xb, state["w_share"])
        r.block_until_ready()
        t2 = time.monotonic()
        if spans is not None:
            spans.append(["compute", t1, t2])
        result = np.asarray(r, dtype=np.int32)
        t3 = time.monotonic()
        if spans is not None:
            spans.append(["serialize", t2, t3])
        tr.send(MASTER,
                WorkerResult(msg.round, args.worker,
                             compute_s=time.monotonic() - t0,
                             payload=result, trace=spans))
        if spans is not None:
            state["carry"] = [["send", t3, time.monotonic()]]

    try:
        while not tr.peer_closed:
            if not pending:
                if tr.next_delivery(me) is None:
                    continue
                drain()
                continue
            at, msg = pending.popleft()
            if msg.round == SHUTDOWN_ROUND:
                return 0
            if msg.round == PROVISION_ROUND:
                p = msg.payload
                # master opts this worker into span recording (DESIGN.md
                # §11); the spans only reach it over a v2 wire — a v1
                # serialization silently drops the trace field
                state["trace"] = bool(p.get("trace"))
                if p.get("protocol") == "mpc":
                    state["protocol"] = "mpc"
                    state["cfg"] = mpc.MPCConfig(**p["cfg"])
                    state["cbar"] = jnp.asarray(p["cbar"], jnp.int32)
                elif p.get("protocol") == "serve":
                    # serving plane (cluster/serve.py): hold the model share
                    # W̃_i for the deployment's lifetime; every flush ships
                    # a query share X̃_i and the round function is one
                    # bilinear field matmul X̃_i·W̃_i.
                    state["protocol"] = "serve"
                    prime = int(p["p"])
                    state["w_share"] = jnp.asarray(p["w_share"], jnp.int32)
                    state["f"] = jax.jit(
                        lambda xb, ws, _p=prime: field.matmul(xb, ws, _p))
                elif p.get("protocol") == "alcc":
                    # ALCC float logistic (DESIGN.md §14): real shares,
                    # float32 arithmetic, real surrogate coefficients
                    from repro.core.protocol import alcc_engine
                    state["protocol"] = "alcc"
                    cbar = jnp.asarray(p["cbar"], jnp.float32)
                    state["f"] = jax.jit(
                        lambda xb, ws, _c=cbar:
                        alcc_engine.worker_eval(_c, xb, ws))
                elif p.get("protocol") == "alcc_mlp":
                    # ALCC MLP (cluster/alcc_mlp.py): two bilinear phases
                    # selected by round parity, both plain float32 matmuls
                    state["protocol"] = "alcc_mlp"
                    state["f_fwd"] = jax.jit(lambda xb, ws: xb @ ws)
                    state["f_bwd"] = jax.jit(lambda xb, ws: xb.T @ ws)
                else:
                    # worker compute never needs the sharded backend; the
                    # path is this host's (the fused kernel on a TPU, the
                    # jnp reference elsewhere), identical mod p (DESIGN.md
                    # §4).
                    state["protocol"] = "cpml"
                    cfg = CPMLConfig(**p["cfg"])
                    # jit the round evaluation: eager op-by-op dispatch of
                    # the limb matmul costs ~50x the fused kernel per round
                    # and was the bulk of the measured socket "overhead".
                    # jit changes WHEN ops run, never what they compute —
                    # exact int32 field math either way (DESIGN.md §4).
                    state["f"] = jax.jit(compute.worker_fn(
                        cfg, jnp.asarray(p["cbar"], jnp.int32)))
                if state["protocol"] != "serve":
                    # field protocols ship exact int32 shares; the ALCC
                    # modes ship float32 real shares
                    dt = (jnp.float32
                          if str(state["protocol"]).startswith("alcc")
                          else jnp.int32)
                    state["x_share"] = jnp.asarray(p["x_share"], dt)
                if state["protocol"] == "serve":
                    # serve flushes are padded to a FIXED (rows, d) shape
                    # (cluster/serve.py), so this one compile covers every
                    # future flush — no mid-service recompile p99 spikes.
                    rows = int(p["rows"])
                    xw = jnp.zeros((rows, state["w_share"].shape[0]),
                                   jnp.int32)
                    t_c0 = time.monotonic()
                    state["f"](xw, state["w_share"]).block_until_ready()
                    if state["trace"]:
                        state["carry"] = [
                            ["warm_compile", t_c0, time.monotonic()]]
                if str(state["protocol"]).startswith("alcc"):
                    # same warmup-before-ack contract as cpml below; ALCC
                    # round shapes are static floats: logistic
                    # (rows, d) x (d, c), MLP (rows, d) x (d, h) forward
                    # and (rows, d)ᵀ x (rows, h) backward
                    x_share = state["x_share"]
                    rows = int(p["cfg"].get("batch_rows")
                               or x_share.shape[0])
                    xw = jnp.zeros((rows, x_share.shape[1]), jnp.float32)
                    t_c0 = time.monotonic()
                    if state["protocol"] == "alcc":
                        ww = jnp.zeros((x_share.shape[1],
                                        int(p["cfg"]["c"])), jnp.float32)
                        state["f"](xw, ww).block_until_ready()
                    else:
                        h = int(p["hidden"])
                        w1 = jnp.zeros((x_share.shape[1], h), jnp.float32)
                        dz = jnp.zeros((rows, h), jnp.float32)
                        state["f_fwd"](xw, w1).block_until_ready()
                        state["f_bwd"](xw, dz).block_until_ready()
                    if state["trace"]:
                        state["carry"] = [
                            ["warm_compile", t_c0, time.monotonic()]]
                if state["protocol"] == "cpml":
                    # compile BEFORE acking: provisioning is the documented
                    # warmup window (rounds start only after every ack, so
                    # round-0 timing never absorbs XLA compilation).  Round
                    # shapes are static: (batch_rows|mk, d) x (d, c, r).
                    x_share = state["x_share"]
                    rows = (cfg.batch_rows if cfg.batch_rows is not None
                            else x_share.shape[0])
                    xw = x_share[jnp.zeros(rows, jnp.int32)]
                    ww = jnp.zeros((x_share.shape[1], cfg.c, cfg.r),
                                   jnp.int32)
                    t_c0 = time.monotonic()
                    state["f"](xw, ww).block_until_ready()
                    if state["trace"]:
                        # ships with the first traced result: the warmup
                        # the provisioning barrier absorbed (the master's
                        # cpml_xla_warm_compile_seconds gauge reads it)
                        state["carry"] = [
                            ["warm_compile", t_c0, time.monotonic()]]
                tr.send(MASTER, Heartbeat(args.worker, time.monotonic()))
                continue
            if args.die_at_round is not None \
                    and msg.round >= args.die_at_round:
                return 0                # crash: no heartbeat, no result
            tr.send(MASTER, Heartbeat(args.worker, time.monotonic()))
            if state["protocol"] is None:
                raise RuntimeError(
                    f"{me}: round {msg.round} share arrived before "
                    f"provisioning")
            if state["protocol"] == "mpc":
                mpc_round(at, msg)
            elif state["protocol"] == "serve":
                serve_round(at, msg)
            elif str(state["protocol"]).startswith("alcc"):
                alcc_round(at, msg)
            else:
                cpml_round(at, msg)
        return 0
    finally:
        tr.close()


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return serve(args)
    except OSError as e:
        print(f"cpml_worker {args.worker}: cannot reach master at "
              f"{args.host}:{args.port}: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
