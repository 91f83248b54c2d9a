"""Coded prediction service driver: simulated OR real multi-process serving.

    python -m repro.launch.cpml_serve --queries 64 --rate 200
    python -m repro.launch.cpml_serve --mode closed --queries 32
    python -m repro.launch.cpml_serve --straggle-worker 7 \\
        --straggle-sleep 0.5 --collect-all
    python -m repro.launch.cpml_serve --transport socket --queries 32
    python -m repro.launch.cpml_serve --transport socket --kill-worker 5 \\
        --kill-at-round 3
    python -m repro.launch.cpml_serve --trace-out serve.trace.json \\
        --metrics-out serve.prom

Runs the privacy-preserving prediction plane (cluster/serve.py): the model
is Lagrange-encoded ONCE and provisioned to N workers, then an open-loop
(Poisson arrivals at ``--rate`` qps) or closed-loop (``--mode closed``,
one saturated batch in flight at a time) client load is admitted into the
bounded request queue, flushed under the max-batch/max-wait policy, and
decoded at the first 2(K+T-1)+1 responders.  Every run reports queries/s
and latency p50/p99 under BOTH wait policies — the first-threshold service
and the wait-for-all counterfactual from the same responder traces — plus
a bit-identity check of the served predictions against the uncoded
plaintext oracle.

``--transport inprocess`` (default) simulates workers under ``--latency``;
``--straggle-worker i`` adds ``--straggle-sleep`` seconds to worker i on
EITHER backend (simulated additive sleep, or a real time.sleep in the
worker process), and ``--kill-worker`` crashes a real worker mid-service
to demo first-threshold decode riding through a death.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="CodedPrivateML prediction-serving driver")
    ap.add_argument("--workers", "-N", type=int, default=8)
    ap.add_argument("--parallel", "-K", type=int, default=2)
    ap.add_argument("--privacy", "-T", type=int, default=1)
    ap.add_argument("--d", type=int, default=32, help="feature dimension")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=32,
                    help="rows per coded flush (K must divide it)")
    ap.add_argument("--max-wait", type=float, default=0.02,
                    help="seconds the oldest admitted query may wait "
                         "before a partial flush")
    ap.add_argument("--queue-cap", type=int, default=64,
                    help="admitted-but-unflushed query bound (a full "
                         "queue rejects at submission)")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--rows", type=int, default=4,
                    help="feature rows per query (open loop)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrival rate, queries/s (Poisson)")
    ap.add_argument("--mode", choices=("open", "closed"), default="open",
                    help="open = scheduled arrivals through the batching "
                         "policy; closed = one full-batch query in flight "
                         "at a time (throughput ceiling)")
    ap.add_argument("--transport", choices=("inprocess", "socket"),
                    default="inprocess")
    ap.add_argument("--latency", choices=("deterministic", "lognormal",
                                          "bursty"),
                    default="lognormal",
                    help="per-worker latency profile (inprocess only)")
    ap.add_argument("--latency-seed", type=int, default=0)
    ap.add_argument("--latency-base", type=float, default=0.01,
                    help="latency model base seconds (inprocess only; "
                         "serving rounds are much lighter than training)")
    ap.add_argument("--straggle-worker", type=int, default=None,
                    help="add --straggle-sleep seconds to this worker "
                         "(both backends)")
    ap.add_argument("--straggle-sleep", type=float, default=0.25)
    ap.add_argument("--kill-worker", type=int, default=None,
                    help="crash this worker index mid-service (socket only)")
    ap.add_argument("--kill-at-round", type=int, default=2,
                    help="flush index at which --kill-worker crashes")
    ap.add_argument("--collect-all", action="store_true",
                    help="keep each flush open until every dispatched "
                         "worker responds, so the wait-for-all "
                         "counterfactual is measured (do not combine "
                         "with --kill-worker)")
    ap.add_argument("--round-timeout", type=float, default=math.inf)
    ap.add_argument("--heartbeat-timeout", type=float, default=math.inf)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--wire", choices=("v1", "v2"), default="v2")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the per-flush bit-identity check vs the "
                         "uncoded plaintext oracle")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json-out", type=str, default=None)
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Perfetto/Chrome trace with per-query "
                         "queue/batch/dispatch/decode spans here")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the serve_* metrics registry here "
                         "(*.json = snapshot, else Prometheus text)")
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    import jax

    from repro.cluster import make_latency
    from repro.cluster.latency import SleepyStragglerLatency
    from repro.cluster.serve import (
        PredictionServer, ServeConfig, open_loop_queries)
    from repro.launch import device
    from repro.launch.cpml_cluster import (
        _json_finite, _recorder_for, local_socket_cluster)

    print(device.device_line())
    device.enable_compile_cache()

    cfg = ServeConfig(N=args.workers, K=args.parallel, T=args.privacy,
                      max_batch=args.max_batch, max_wait_s=args.max_wait,
                      queue_cap=args.queue_cap)
    mode = (args.latency if args.transport == "inprocess"
            else f"socket x{cfg.N} procs")
    print(f"CPML serve: N={cfg.N} K={cfg.K} T={cfg.T} "
          f"threshold={cfg.threshold} max_batch={cfg.max_batch} "
          f"max_wait={cfg.max_wait_s * 1e3:.0f}ms [{mode}, {args.mode} loop]")

    # stand-in for a trained model head; any (d, classes) weights serve
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(args.seed),
                                (args.d, args.classes))
    key = jax.random.PRNGKey(args.seed + 1)
    rows = cfg.max_batch if args.mode == "closed" else args.rows
    rate = 0.0 if args.mode == "closed" else args.rate
    queries = open_loop_queries(args.queries, rows=rows, d=args.d,
                                rate_qps=rate, seed=args.seed + 2)
    kw = dict(round_timeout_s=args.round_timeout,
              heartbeat_timeout_s=args.heartbeat_timeout,
              collect_all=args.collect_all, verify=not args.no_verify,
              recorder=_recorder_for(args))

    if args.transport == "socket":
        die = ({args.kill_worker: args.kill_at_round}
               if args.kill_worker is not None else None)
        sleep = ({args.straggle_worker: args.straggle_sleep}
                 if args.straggle_worker is not None else None)
        with local_socket_cluster(cfg.N, port=args.port, die_at_round=die,
                                  sleep_s=sleep,
                                  wire_version=int(args.wire[1:])) as tr:
            srv = PredictionServer(cfg, w, key, transport=tr, **kw)
            srv.provision()
            t0 = time.monotonic()
            if args.mode == "closed":
                srv.run_closed_loop(queries)
            else:
                srv.run(queries)
            wall_s = time.monotonic() - t0
            srv.shutdown_workers()
        print(f"socket service: {len(srv.results)} queries over TCP "
              f"in {wall_s:.1f}s")
        if die:
            print(f"killed worker {args.kill_worker} at flush "
                  f"{args.kill_at_round}: first-threshold decode rode "
                  f"through")
    else:
        latency = make_latency(args.latency, seed=args.latency_seed,
                               base=args.latency_base)
        if args.straggle_worker is not None:
            latency = SleepyStragglerLatency(
                latency, {args.straggle_worker: args.straggle_sleep})
        srv = PredictionServer(cfg, w, key, latency=latency, **kw)
        if args.mode == "closed":
            srv.run_closed_loop(queries)
        else:
            srv.run(queries)

    stats = srv.stats()
    first, allw = stats["latency_first"], stats["latency_all"]
    word = "wall" if args.transport == "socket" else "simulated"
    print(f"served {stats['queries']}/{args.queries} queries "
          f"({stats['rejected']} rejected) in {stats['rounds']} flushes: "
          f"{stats['queries_per_s']:.1f} queries/s, "
          f"{stats['rows_per_s']:.0f} rows/s ({word})")
    print(f"latency first-threshold: p50 {first['p50'] * 1e3:.1f}ms  "
          f"p99 {first['p99'] * 1e3:.1f}ms")
    if allw["n"]:
        print(f"latency wait-for-all:    p50 {allw['p50'] * 1e3:.1f}ms  "
              f"p99 {allw['p99'] * 1e3:.1f}ms "
              f"({allw['unobserved']} unobserved)")
    elif allw["unobserved"]:
        print(f"(wait-for-all unobserved on every flush: rerun with "
              f"--collect-all to measure the counterfactual)")

    rc = 0
    if not args.no_verify:
        ok = stats["oracle"]["bit_identical"] and stats["oracle"]["checked"]
        print(f"served predictions bit-identical to the uncoded plaintext "
              f"oracle: {bool(ok)} ({stats['oracle']['checked']} flushes)")
        if not ok:
            rc = 1

    if args.trace_out:
        from repro.obs.export import (straggler_report, waterfall,
                                      write_chrome_trace)
        obj = write_chrome_trace(srv.obs, args.trace_out)
        pids = {e.get("pid") for e in obj["traceEvents"]}
        print(f"trace: {len(obj['traceEvents'])} events / {len(pids)} "
              f"process(es) -> {args.trace_out} (load at ui.perfetto.dev)")
        print(waterfall(srv.obs))
        text, _ = straggler_report(srv.traces, cfg.threshold)
        print(text)
    if args.metrics_out:
        srv.metrics.write(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(_json_finite(
                {"config": {"N": cfg.N, "K": cfg.K, "T": cfg.T,
                            "threshold": cfg.threshold,
                            "max_batch": cfg.max_batch,
                            "max_wait_s": cfg.max_wait_s,
                            "queue_cap": cfg.queue_cap,
                            "transport": args.transport,
                            "mode": args.mode,
                            "latency": (args.latency
                                        if args.transport == "inprocess"
                                        else None)},
                 "stats": stats}), f, indent=2)
    return rc


if __name__ == "__main__":
    sys.exit(main())
