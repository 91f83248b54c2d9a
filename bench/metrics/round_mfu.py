"""The whole round's share of the chips' int8 peak: field operations the
coded rounds of the window require (worker polynomial, weight encode,
decode; ``bench/shapes.py``), over the window and the chips, in %."""

from bench import shapes


def read(m):
    if not m.rounds or m.window_s <= 0:
        return None
    return shapes.round_mfu(m.config, m.rounds, m.window_s, m.chips, m.peaks)
