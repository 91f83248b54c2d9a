"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is read once into plain lists of events; every reduction below
works on those lists, so it is checked on small synthetic traces in
``bench/tests``. Device planes are those named ``/device:...``; on a TPU
each has a line of XLA operations and a line of XLA modules (one event per
program run). Host planes hold the benchmark's own ``TraceAnnotation``s,
all named ``bench_...``, and the runtime's own host events.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
ANNOTATION_PREFIX = "bench_"
# operations that only contain others (a scan's loop): they count towards
# busy time through their body, and are left out of the list of top ops
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]       # device plane -> operations
    modules: dict[str, list[Event]]   # device plane -> program runs
    host: list[Event]                 # every host event, all threads

    def annotation(self, name: str) -> Event:
        found = [e for e in self.host if e.name == name]
        if not found:
            raise LookupError(f"no host annotation {name!r} in the trace")
        return max(found, key=lambda e: e.duration_ns)


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` that a profiler session wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise LookupError(f"expected one trace under {log_dir}, got {paths}")
    data = ProfileData.from_file(paths[0])
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in OPS_LINES:
                sink = ops.setdefault(plane.name, [])
            elif device and line.name in MODULE_LINES:
                sink = modules.setdefault(plane.name, [])
            elif plane.name.startswith("/host:"):
                sink = host
            else:
                continue
            for e in line.events:
                sink.append(Event(e.name, float(e.start_ns),
                                  float(e.end_ns)))
    return Trace(ops=ops, modules=modules, host=host)


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    return [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
            for e in events if e.end_ns > lo and e.start_ns < hi]


def union_ns(events: list[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    total, cur_lo, cur_hi = 0.0, None, None
    for e in sorted(clip(events, lo, hi), key=lambda e: e.start_ns):
        if cur_hi is None or e.start_ns > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = e.start_ns, e.end_ns
        else:
            cur_hi = max(cur_hi, e.end_ns)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals inside [lo, hi] that no event covers."""
    out, t = [], lo
    for e in sorted(clip(events, lo, hi), key=lambda e: e.start_ns):
        if e.start_ns > t:
            out.append((t, e.start_ns))
        t = max(t, e.end_ns)
    if t < hi:
        out.append((t, hi))
    return out


def busy_s(trace: Trace, lo: float, hi: float) -> dict[str, float]:
    """Seconds in which an operation ran, per device plane."""
    return {dev: union_ns(evs, lo, hi) / 1e9 for dev, evs in trace.ops.items()}


def sum_by_name(events: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds per event name inside [lo, hi]."""
    out: dict[str, float] = {}
    for e in clip(events, lo, hi):
        out[e.name] = out.get(e.name, 0.0) + e.duration_ns / 1e9
    return out


def matching(events: list[Event], needle: str) -> list[Event]:
    needle = needle.lower()
    return [e for e in events if needle in e.name.lower()]


def module_runs(trace: Trace, needle: str) -> dict[str, list[Event]]:
    """Per device, the runs of the programs whose name contains ``needle``."""
    return {dev: found for dev, evs in trace.modules.items()
            if (found := matching(evs, needle))}


def opcode(name: str) -> str:
    """The HLO opcode of an operation event named by its HLO text, as in
    ``%while.8 = (s32[], ...) while(...)``; '' where the name is not HLO."""
    if " = " not in name:
        return ""
    rhs = name.split(" = ", 1)[1]
    depth = 0
    for i, ch in enumerate(rhs):       # skip the (possibly tuple) shape
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rhs[i + 1:].split("(", 1)[0]
    return ""


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """The n operation names with most device time, averaged over the
    devices."""
    total: dict[str, float] = {}
    for evs in trace.ops.values():
        evs = [e for e in evs if opcode(e.name) not in CONTAINERS]
        for name, s in sum_by_name(evs, lo, hi).items():
            total[name] = total.get(name, 0.0) + s / len(trace.ops)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _describe(around: list[Event]) -> str:
    ours = sorted((e for e in around if e.name.startswith(ANNOTATION_PREFIX)),
                  key=lambda e: -e.duration_ns)
    other = sorted((e for e in around
                    if not e.name.startswith(ANNOTATION_PREFIX)),
                   key=lambda e: e.duration_ns)
    return " > ".join([e.name for e in ours] + [e.name for e in other[:1]]) \
        or "no host event"


def host_activity(trace: Trace, times: list[float]) -> list[str]:
    """What the host was doing at each of the sorted ``times``: the
    benchmark's annotations around it, outermost first, then the innermost
    other host event. One sweep over the host events."""
    evs = sorted(trace.host, key=lambda e: e.start_ns)
    out, active, i = [], [], 0
    for t in times:
        while i < len(evs) and evs[i].start_ns <= t:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e.end_ns > t]
        out.append(_describe(active))
    return out


def program_name(name: str) -> str:
    """``jit_matmul(1500...)`` -> ``jit_matmul``."""
    return name.split("(", 1)[0]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list[list]:
    """Idle seconds of the first device, summed by what the host was doing
    halfway through each gap and by the program the device ran next; the n
    largest sums."""
    if not trace.ops:
        return []
    dev = sorted(trace.ops)[0]
    starts = sorted((e.start_ns, program_name(e.name))
                    for e in trace.modules.get(dev, []))
    start_ns = [t for t, _ in starts]
    found = gaps(trace.ops[dev], lo, hi)
    doing = host_activity(trace, [(a + b) / 2 for a, b in found])
    total: dict[str, float] = {}
    for (a, b), host in zip(found, doing):
        i = bisect.bisect_left(start_ns, b)
        nxt = starts[i][1] if i < len(starts) else "window end"
        key = f"{host} -> {nxt}"
        total[key] = total.get(key, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
