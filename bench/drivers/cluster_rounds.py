"""Coded rounds driven one after another by the master runtime,
``ClusterRunner`` on the in-process transport, under a seeded straggler
latency model (``make_latency``). The runner is built in set-up, which
encodes the dataset once. Arrivals are simulated, so a round's wall time is
the master's host work plus the device round. Each round is timed from the
start of its round call until its weights are ready: a master cannot encode
round t+1 before it holds w_t.

Traffic keys: ``latency`` (a ``make_latency`` model name), ``latency_args``
(its keyword arguments; the seed is the run's), ``warmup_rounds``.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from bench import data, program

# step_round's horizon only sizes mini-batch and prefetch schedules, which
# this full-batch, unpipelined traffic does not use.
HORIZON = 1 << 30


@dataclasses.dataclass
class State:
    runner: object
    key: jax.Array
    rounds: int = 0

    def run_round(self) -> None:
        with jax.profiler.TraceAnnotation("bench_round"):
            self.runner.step_round(self.rounds, HORIZON)
            jax.block_until_ready(self.runner.w2)
        self.rounds += 1


def setup(run) -> State:
    from repro.cluster import ClusterRunner, make_latency
    cfg = program.coded_config(run.config, run.chips, run.traffic)
    latency = make_latency(run.traffic["latency"], seed=run.seed,
                           **run.traffic.get("latency_args", {}))
    key = data.stream(run.seed, data.CLUSTER)
    with program.layout(cfg, run.devices):
        state = State(runner=ClusterRunner(cfg, key, run.x, run.y, latency),
                      key=key)
        for _ in range(int(run.traffic["warmup_rounds"])):
            state.run_round()
    return state


def window(run, state: State, seconds: float) -> dict:
    times = []
    cfg = state.runner.cfg
    with program.layout(cfg, run.devices):
        t0 = time.perf_counter()
        while True:
            r0 = time.perf_counter()
            state.run_round()
            now = time.perf_counter()
            times.append(now - r0)
            if now - t0 >= seconds:
                break
    return {"elapsed_s": now - t0, "rounds": len(times),
            "attempted": len(times), "failed": 0, "round_s": times}


def answers(state: State) -> list:
    """The runner's weights after every round it ran: (key, rounds, w)."""
    return [(state.key, state.rounds, state.runner.w2)]
