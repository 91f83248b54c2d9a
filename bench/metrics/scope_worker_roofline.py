"""Share of the roofline of one round's worker polynomial, read from the
window's own ops: the least time the chips could take for the work and
bytes the algorithm's shapes require (``bench/shapes.py``), over the device
ms a round of the ops the program names ``cpml_worker``, the mean over the
chips (on several chips that scope holds the results' all-gather too).
Says which bound applies."""

from bench import shapes, spans


def read(m):
    ms = spans.scope_ms(m, "cpml_worker")
    if not ms:
        return None
    share, bound = shapes.worker_roofline(m.config, ms / 1e3, m.chips,
                                          m.peaks)
    return {"value": share, "bound": bound}
