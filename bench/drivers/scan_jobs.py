"""Back-to-back training jobs through ``protocol.train``, the entry
``cpml_train`` calls: full batch, every worker answering in index order,
a fresh key per job. Each job pays its own dataset encode, step size and
schedule, as in the paper's total-time accounting.

Traffic keys: ``iters`` (rounds per job), ``warmup_jobs`` (run in set-up,
compiling this cell's shapes and nothing else).
"""
from __future__ import annotations

import dataclasses
import time

import jax

from bench import data, program


@dataclasses.dataclass
class State:
    cfg: object
    devices: list
    x: jax.Array
    y: jax.Array
    iters: int
    jobs_key: jax.Array
    jobs: list = dataclasses.field(default_factory=list)  # (key, rounds, w)

    def run_job(self) -> None:
        key = jax.random.fold_in(self.jobs_key, len(self.jobs))
        with jax.profiler.TraceAnnotation("bench_job"):
            w, _ = program.protocol.train(self.cfg, key, self.x, self.y,
                                          self.iters)
            w = jax.block_until_ready(w)
        self.jobs.append((key, self.iters, w))


def setup(run) -> State:
    cfg = program.coded_config(run.config, run.chips, run.traffic)
    state = State(cfg=cfg, devices=run.devices, x=run.x, y=run.y,
                  iters=int(run.traffic["iters"]),
                  jobs_key=data.stream(run.seed, data.JOBS))
    with program.layout(state.cfg, state.devices):
        for _ in range(int(run.traffic["warmup_jobs"])):
            state.run_job()
    return state


def window(run, state: State, seconds: float) -> dict:
    """Jobs until ``seconds`` have passed; the job in progress then ends
    the window, so no partial job is counted."""
    first = len(state.jobs)
    with program.layout(state.cfg, state.devices):
        t0 = time.perf_counter()
        while True:
            state.run_job()
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    jobs = len(state.jobs) - first
    return {"elapsed_s": elapsed, "rounds": jobs * state.iters,
            "attempted": jobs * state.iters, "failed": 0, "round_s": None,
            "jobs": jobs}


def answers(state: State) -> list:
    """Every job's weights, the warm-up's too: (key, rounds, w)."""
    return state.jobs
