"""The comparison that decides ``correct``.

Every answer the timed path produced (a training job's weights, or the
cluster runner's weights after its rounds) is recomputed by the
configuration's plain reference from the same key and round count, and the
worst relative gap ||w - w_ref|| / ||w_ref|| over the answers is held
against the cell's limit (``bench/limits/<workload>.json``). A number with
no limit, or one that is not finite, is not correct.
"""
from __future__ import annotations

import math

import numpy as np


def rel_err(w, w_ref) -> float:
    w_ref = np.asarray(w_ref, np.float64)
    w = np.asarray(w, np.float64).reshape(w_ref.shape)
    return float(np.linalg.norm(w - w_ref) / np.linalg.norm(w_ref))


def readings(reference, config: dict, x, y, answers,
             precision: str | None = None) -> dict[str, float]:
    """The numbers compared, here the worst relative gap over the answers.

    ``answers`` holds (key, rounds, w) of the program, already on the host.
    """
    precision = precision or reference.EXACT
    worst = 0.0
    for key, rounds, w in answers:
        w_ref = reference.train(config, x, y, key, rounds, precision)
        worst = max(worst, rel_err(w, w_ref))
    return {"w_rel_err": worst}


def decide(numbers: dict[str, float], limits: dict[str, float]
           ) -> tuple[bool, dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}), each number beside its limit."""
    shown, ok = {}, bool(numbers)
    for name, value in numbers.items():
        limit = limits.get(name)
        shown[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and math.isfinite(value) \
            and value <= limit
    return ok, shown
