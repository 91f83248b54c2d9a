"""Host ms of the master's own work in a cluster round: from the start of
the program's ``cpml.round`` phase to the end of its ``cpml.round_program``
(the round's device program dispatched), the mean over rounds. Further
keys: the mean ms a round of the decode-matrix phase, of the scheduler's
dispatch and collect, of the membership fence and of the round key's
derivation; decode-matrix solves a
round (``cpml.decode_solve`` opens on a cache miss only); the device's idle
ms a round inside the benchmark's round, and the share of it under a phase
other than ``cpml.round``, with the idle ms a round under each."""

from bench import spans

OUTER = "bench_round"


def read(m):
    out = spans.master_round(m.window, m.lo_ns, m.hi_ns)
    if out is None:
        return None
    idle = spans.idle_by_phase(m.window, m.lo_ns, m.hi_ns, OUTER)
    total = sum(idle.values())
    if total > 0:
        n = out["rounds"]
        out["idle_ms"] = total * 1e3 / n
        out["idle_named_share"] = 100.0 * (1 - idle.get("", 0.0) / total)
        for name, s in sorted(idle.items()):
            key = name[len(spans.PHASE_PREFIX):] if name else "unnamed"
            out[f"idle_{key}_ms"] = s * 1e3 / n
    return out
