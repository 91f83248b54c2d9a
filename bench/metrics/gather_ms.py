"""Device ms per round of the all-gather operations in the measured window,
the mean over the chips. Nothing to read on one chip."""

import statistics

from bench import trace as tr


def read(m):
    if m.chips < 2 or not m.rounds:
        return None
    per_chip = [sum(e.duration_ns for e in tr.clip(
                    tr.matching(evs, "all-gather"), m.lo_ns, m.hi_ns))
                for evs in m.window.ops.values()]
    if not any(per_chip):
        return None
    return statistics.fmean(per_chip) / 1e6 / m.rounds
