"""What a traced run hands to the per-layer metric readers.

Each reader in ``bench/metrics/<name>.py`` has ``read(m: Measured)`` and
returns a number, a dict with a ``value`` and further keys, or None where it
finds nothing to read: the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import dataclasses
import statistics

from bench import trace as tr


@dataclasses.dataclass
class Measured:
    config: dict                  # the configuration's sizes
    chips: int
    peaks: dict                   # one chip's peaks (peaks.json)
    rounds: int                   # coded rounds completed in the window
    window: tr.Trace              # trace of the measured window
    lo_ns: float                  # the window's bounds on the trace clock
    hi_ns: float
    probes: tr.Trace | None       # trace of the layer probes
    probe_calls: dict[str, int]   # probe program name -> calls traced
    host_spans_s: dict[str, float]  # host-clock span name -> mean seconds

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    def busy_s(self) -> dict[str, float]:
        return tr.busy_s(self.window, self.lo_ns, self.hi_ns)

    def mean_busy_s(self) -> float:
        busy = self.busy_s()
        return statistics.fmean(busy.values()) if busy else 0.0

    def probe_ms(self, name: str) -> float | None:
        """Device milliseconds per call of the probe program ``name``, the
        mean over the devices that ran it."""
        if self.probes is None or not self.probe_calls.get(name):
            return None
        runs = tr.module_runs(self.probes, name)
        if not runs:
            return None
        per_dev = [sum(e.duration_ns for e in evs) / len(evs) / 1e6
                   for evs in runs.values()]
        return statistics.fmean(per_dev)
