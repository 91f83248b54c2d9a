"""Reduce the program's own phases in a trace to the numbers the per-layer
metrics read.

The program names its phases itself (``repro.obs.trace.phase``, DESIGN.md
§11): host phases are profiler annotations ``cpml.<name>`` on the same
clock as the device planes, and the device ops of a round carry the scope
of ``jax.named_scope`` (``cpml_worker``, ``cpml_encode_weights``,
``cpml_decode``) in the ``op_name`` metadata of the compiled HLO. An op
event names its HLO instruction; the instruction's ``op_name`` is read from
the HLO text of the program that ran it, as the process holds it compiled.

A program that opens none of these phases or scopes leaves nothing to read:
every function here then returns None or an empty result, and never raises.
"""
from __future__ import annotations

import bisect
import re
import statistics

from bench import trace as tr

PHASE_PREFIX = "cpml."
ROUND = "cpml.round"
ROUND_PROGRAM = "cpml.round_program"
TRAIN = "cpml.train"
SETUP_PREFIX = "cpml.setup."
SCAN_PROGRAM = "_train_scan"
# the cluster round's host phases read by master_host_ms, metric key first
MASTER_PARTS = {"decode_matrix_ms": ("cpml.decode_matrix",),
                "scheduler_ms": ("cpml.dispatch", "cpml.collect"),
                "fence_ms": ("cpml.fence",),
                "round_key_ms": ("cpml.round_key",)}
SOLVE = "cpml.decode_solve"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


# ---------------------------------------------------------------------------
# Host phases
# ---------------------------------------------------------------------------

def within(events: list[tr.Event], name: str, lo: float, hi: float
           ) -> list[tr.Event]:
    """The events called ``name`` that lie wholly inside [lo, hi], by start."""
    return sorted((e for e in events if e.name == name
                   and e.start_ns >= lo and e.end_ns <= hi),
                  key=lambda e: e.start_ns)


def _inside(events: list[tr.Event], outer: tr.Event) -> list[tr.Event]:
    return [e for e in events
            if e.start_ns >= outer.start_ns and e.end_ns <= outer.end_ns]


def master_round(trace: tr.Trace, lo: float, hi: float) -> dict | None:
    """Mean host ms a cluster round: from the start of ``cpml.round`` to the
    end of its ``cpml.round_program`` (the dispatch of the round's device
    program), with the mean ms a round of each part of ``MASTER_PARTS`` and
    the count of decode-matrix solves a round. None without such rounds."""
    rounds = within(trace.host, ROUND, lo, hi)
    programs = within(trace.host, ROUND_PROGRAM, lo, hi)
    host_ns = []
    for r in rounds:
        mine = _inside(programs, r)
        if mine:
            host_ns.append(max(e.end_ns for e in mine) - r.start_ns)
    if not host_ns:
        return None
    n = len(rounds)
    out = {"value": statistics.fmean(host_ns) / 1e6, "rounds": n}
    for key, names in MASTER_PARTS.items():
        out[key] = sum(e.duration_ns for name in names
                       for e in within(trace.host, name, lo, hi)) / 1e6 / n
    out["solves_per_round"] = len(within(trace.host, SOLVE, lo, hi)) / n
    return out


def job_setup(trace: tr.Trace, lo: float, hi: float) -> dict | None:
    """Mean host ms of a training job's own set-up: from the start of
    ``cpml.train`` to the start of that job's scan program on the device,
    the mean over jobs and chips, with the mean ms of each ``cpml.setup.*``
    phase a job. None without a job whose scan ran on a device."""
    jobs = within(trace.host, TRAIN, lo, hi)
    per_chip = []
    for runs in tr.module_runs(trace, SCAN_PROGRAM).values():
        starts = sorted(e.start_ns for e in runs)
        waits = []
        for job in jobs:
            i = bisect.bisect_left(starts, job.start_ns)
            if i < len(starts) and starts[i] < job.end_ns:
                waits.append(starts[i] - job.start_ns)
        if waits:
            per_chip.append(statistics.fmean(waits))
    if not per_chip:
        return None
    out = {"value": statistics.fmean(per_chip) / 1e6, "jobs": len(jobs)}
    names = sorted({e.name for e in trace.host
                    if e.name.startswith(SETUP_PREFIX)})
    for name in names:
        spans = [e for job in jobs
                 for e in _inside(within(trace.host, name, lo, hi), job)]
        out[name[len(SETUP_PREFIX):] + "_ms"] = \
            sum(e.duration_ns for e in spans) / 1e6 / len(jobs)
    return out


class _Sorted:
    """Events sorted by start, for the ones that touch an interval."""

    def __init__(self, events: list[tr.Event]):
        self.events = sorted(events, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.events]
        self.reach, top = [], float("-inf")   # running max of the ends
        for e in self.events:
            top = max(top, e.end_ns)
            self.reach.append(top)

    def touching(self, lo: float, hi: float) -> list[tr.Event]:
        i = bisect.bisect_right(self.reach, lo)
        j = bisect.bisect_left(self.starts, hi)
        return [e for e in self.events[i:j] if e.end_ns > lo]


def idle_by_phase(trace: tr.Trace, lo: float, hi: float, outer: str
                  ) -> dict[str, float]:
    """Idle seconds of the first device inside the host annotations called
    ``outer``, each idle instant given to the innermost (shortest) program
    phase open then, ``cpml.round`` itself left out; '' where none is open.
    Empty without a device or an ``outer`` annotation."""
    if not trace.ops:
        return {}
    ops = _Sorted(tr.clip(trace.ops[sorted(trace.ops)[0]], lo, hi))
    phases = _Sorted([e for e in trace.host if e.name.startswith(PHASE_PREFIX)
                      and e.name != ROUND])
    out: dict[str, float] = {}
    for o in within(trace.host, outer, lo, hi):
        mine = phases.touching(o.start_ns, o.end_ns)
        for a, b in tr.gaps(ops.touching(o.start_ns, o.end_ns), o.start_ns,
                            o.end_ns):
            cuts = sorted({a, b} | {t for e in mine
                                    for t in (e.start_ns, e.end_ns)
                                    if a < t < b})
            for s, t in zip(cuts, cuts[1:]):
                open_ = [e for e in mine if e.start_ns <= s and e.end_ns >= t]
                name = (min(open_, key=lambda e: e.duration_ns).name
                        if open_ else "")
                out[name] = out.get(name, 0.0) + (t - s) / 1e9
    return out


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------

def instruction(event_name: str) -> str:
    """The HLO instruction an op event names: ``%fusion.3 = s32[2] ...`` or
    ``fusion.3`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_names_from_hlo(text: str) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, from HLO text."""
    out = {}
    for line in text.splitlines():
        got = _INSTR.match(line)
        if got:
            out[got.group(1)] = got.group(2)
    return out


def live_op_names(modules: set[str]) -> dict[str, dict[str, str]]:
    """Program name -> (instruction -> op_name) for the named programs that
    this process holds compiled. An instruction whose op_name differs
    between two programs of one name is left out."""
    import jax.extend
    out: dict[str, dict[str, str]] = {}
    clash: dict[str, set[str]] = {}
    for exe in jax.extend.backend.get_backend().live_executables():
        try:
            hlo = exe.hlo_modules()
        except Exception:          # noqa: BLE001 - an executable without
            continue               # HLO names no op of the trace
        for mod in hlo:
            if mod.name not in modules:
                continue
            names = op_names_from_hlo(mod.to_string())
            have = out.setdefault(mod.name, {})
            for instr, op_name in names.items():
                if have.get(instr, op_name) != op_name:
                    clash.setdefault(mod.name, set()).add(instr)
                have[instr] = op_name
    for mod, instrs in clash.items():
        for instr in instrs:
            out[mod].pop(instr, None)
    return out


def window_programs(trace: tr.Trace, lo: float, hi: float) -> set[str]:
    return {tr.program_name(e.name) for evs in trace.modules.values()
            for e in tr.clip(evs, lo, hi)}


def in_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")


def scope_device_s(trace: tr.Trace, lo: float, hi: float, scope: str,
                   op_names: dict[str, dict[str, str]]) -> dict[str, float]:
    """Per device plane, seconds inside [lo, hi] of the ops whose op_name
    lies under ``scope``; ops that only contain others are left out, their
    bodies count."""
    out = {}
    for dev, evs in trace.ops.items():
        runs = sorted(trace.modules.get(dev, []), key=lambda e: e.start_ns)
        run_starts = [e.start_ns for e in runs]
        total = 0.0
        for e in tr.clip(evs, lo, hi):
            if tr.opcode(e.name) in tr.CONTAINERS:
                continue
            i = bisect.bisect_right(run_starts, e.start_ns) - 1
            if i < 0 or runs[i].end_ns < e.start_ns:
                continue
            names = op_names.get(tr.program_name(runs[i].name), {})
            if in_scope(names.get(instruction(e.name), ""), scope):
                total += e.duration_ns / 1e9
        out[dev] = total
    return out


def scope_ms(m, scope: str, op_names: dict[str, dict[str, str]] | None = None
             ) -> float | None:
    """Device ms a round of the ops under ``scope`` in the measured window,
    the mean over the chips. ``op_names`` defaults to what this process
    holds compiled. None where no op of the window lies under the scope."""
    if not m.rounds or not m.window.ops:
        return None
    if op_names is None:
        op_names = live_op_names(window_programs(m.window, m.lo_ns, m.hi_ns))
    per_dev = scope_device_s(m.window, m.lo_ns, m.hi_ns, scope, op_names)
    if not any(per_dev.values()):
        return None
    return statistics.fmean(per_dev.values()) * 1e3 / m.rounds
