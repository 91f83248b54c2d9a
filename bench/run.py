"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, driver, metric readers, plain
reference and limits are found by name (``bench/cells.py``). Set-up makes
the data on the device from the seed, builds the program's state and warms
up this cell's shapes; the window then measures for ``--seconds``. With
``--trace 1`` the window is traced instead and the per-layer metrics are
read from it. After the window, the weights the timed path produced are
recomputed by the plain reference and compared (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each number compared beside its limit. The same
numbers are the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells  # noqa: E402

NO_CHIP = 3
PROBE_CALLS = 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    def __init__(self, why: str):
        print(f"bench: {why}", file=sys.stderr, flush=True)
        super().__init__(NO_CHIP)


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell's pieces, the seed and the inputs."""
    cell: cells.Cell
    seed: int
    devices: list
    x: object
    y: object

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def chips(self) -> int:
        return self.cell.chips


def start_jax(root: pathlib.Path):
    """JAX with its persistent compile cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    cached, and the TPU runtime's logs off."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def chip_devices(jax, chips: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"the benchmark has no CPU mode")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compilations while ``active``."""

    def __init__(self, jax):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1


def end_to_end(cell: cells.Cell, win: dict, setup_s: float) -> dict:
    have = {"setup_s": setup_s,
            "round_ms": 1e3 * win["elapsed_s"] / win["rounds"]}
    if win.get("round_s"):
        import numpy as np
        have["round_p95_ms"] = 1e3 * float(np.percentile(win["round_s"], 95))
    return {m["name"]: {"value": have[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def profiled(jax, fn):
    """fn() under the profiler, the Python tracer off; (its result, the
    trace read into memory). The trace's files are deleted."""
    from bench import trace as tr
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        return out, tr.load(log_dir)


def traced_window(jax, run: Run, driver, state, seconds: float):
    """The window under the profiler; returns (window result, trace, lo, hi)."""
    def window():
        with jax.profiler.TraceAnnotation("bench_window"):
            return driver.window(run, state, seconds)

    win, trace = profiled(jax, window)
    span = trace.annotation("bench_window")
    return win, trace, span.start_ns, span.end_ns


def per_layer(cell, run, jax, win, trace, lo, hi, peaks) -> dict:
    from bench import data, measure, program
    wanted = {m["name"] for m in cell.per_layer}
    probes = program.Probes(program.coded_config(cell.config, cell.chips,
                                                cell.traffic),
                            run.devices, run.x,
                            data.stream(run.seed, data.PROBES))
    calls, probe_trace = profiled(
        jax, lambda: probes.run_devices(PROBE_CALLS))
    spans = {}
    if "dataset_encode_ms" in wanted:
        spans["dataset_encode"] = probes.dataset_encode_s()
    del probes
    m = measure.Measured(config=cell.config, chips=cell.chips, peaks=peaks,
                         rounds=win["rounds"], window=trace, lo_ns=lo,
                         hi_ns=hi, probes=probe_trace, probe_calls=calls,
                         host_spans_s=spans)
    out = {}
    for metric in cell.per_layer:
        got = cell.metric_reader(metric["name"]).read(m)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        entry["value"] = float(entry["value"])
        entry["unit"] = metric["unit"]
        out[metric["name"]] = entry
    return out, m


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True, t_start: float = T_START,
             peaks_kind: str | None = None, keep: dict | None = None
             ) -> dict:
    """One run of the cell. Tests skip the look for a chip with
    ``require_tpu=False`` and name the peaks to use with ``peaks_kind``;
    ``keep`` receives the answers and the inputs they were checked on."""
    jax = start_jax(cell.root)
    devices = chip_devices(jax, cell.chips, require_tpu)
    t_chip = time.perf_counter()
    import numpy as np
    from bench import check, data, shapes
    from bench import trace as tr
    peaks = shapes.peaks_for(peaks_kind or devices[0].device_kind)
    compiles = CompileCounter(jax)
    with jax.default_device(devices[0]):
        x, y = data.make_dataset(cell.config, seed)
        t_data = time.perf_counter()
        run = Run(cell=cell, seed=seed, devices=devices, x=x, y=y)
        driver = cell.driver()
        state = driver.setup(run)
        t_setup = time.perf_counter()
        setup_s = t_setup - t_start
        print(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s = "
              f"imports and chip {t_chip - t_start:.3f} s + data "
              f"{t_data - t_chip:.3f} s + program state and warm-up "
              f"{t_setup - t_data:.3f} s", file=sys.stderr, flush=True)
        compiles.active = True
        if not traced:
            win = driver.window(run, state, seconds)
            compiles.active = False
            metrics = end_to_end(cell, win, setup_s)
            extra = {}
        else:
            seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
            win, trace, lo, hi = traced_window(jax, run, driver, state,
                                               seconds)
            compiles.active = False
            metrics, m = per_layer(cell, run, jax, win, trace, lo, hi, peaks)
            extra = {"busy_s": m.mean_busy_s(), "window_s": m.window_s,
                     "breakdown": {"device_ops": tr.top_ops(trace, lo, hi),
                                   "idle_gaps": tr.idle_gaps(trace, lo, hi)}}
            del trace, m
        print(f"bench: window {win['elapsed_s']:.3f} s, {win['rounds']} "
              f"rounds, {compiles.count} compilations in the window",
              file=sys.stderr, flush=True)
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        answers = [(k, r, np.asarray(w)) for k, r, w in driver.answers(state)]
        del state
        gc.collect()
        numbers = check.readings(cell.reference(), cell.config, x, y,
                                 answers)
        if keep is not None:
            keep.update(answers=answers, x=x, y=y)
    correct, shown = check.decide(numbers, cell.limits)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = extra["busy_s"]
        device["window_s"] = extra["window_s"]
        result["breakdown"] = extra["breakdown"]
    result["compilations_in_window"] = compiles.count
    result["checks"] = shown
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, shown in result["checks"].items():
        print(f"check {name}: {shown['value']!r} limit {shown['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
