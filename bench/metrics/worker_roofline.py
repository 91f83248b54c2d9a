"""Share of the roofline of one round's worker polynomial: the least time
the chips could take for the work and bytes the algorithm's shapes require
(``bench/shapes.py``), over ``worker_ms``. Says which bound applies."""

from bench import shapes


def read(m):
    ms = m.probe_ms("bench_worker_step")
    if not ms:
        return None
    share, bound = shapes.worker_roofline(m.config, ms / 1e3, m.chips,
                                          m.peaks)
    return {"value": share, "bound": bound}
