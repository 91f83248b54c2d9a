"""Work and bytes of one coded round, counted from the algorithm's shapes.

Nothing here looks at the implementation: a change of limbs, kernel or
storage does not change these counts, so a share of the roofline or of the
peak computed from them can never read above 100%.

A field multiply-add counts as 2 operations. A field element moves at
ceil(bits(p) / 8) bytes, the least any implementation can store it in:
3 for the paper's 24-bit prime, 4 for the 30-bit one.
"""
from __future__ import annotations

import json
import pathlib


def field_bytes(p: int) -> int:
    return -(-int(p).bit_length() // 8)


def rows_per_part(cfg: dict) -> int:
    """m / K, rounded up: the rows of one coded share."""
    return -(-cfg["m"] // cfg["K"])


def threshold(cfg: dict) -> int:
    """Results the master decodes from: (2r + 1)(K + T - 1) + 1."""
    return (2 * cfg["r"] + 1) * (cfg["K"] + cfg["T"] - 1) + 1


def worker_ops(cfg: dict) -> int:
    """All N workers' f(X̃, W̃) = X̃ᵀ ḡ(X̃ W̃): (m/K) d c r multiply-adds for
    X̃ W̃ and (m/K) d c for X̃ᵀ ḡ, per worker."""
    return (2 * cfg["N"] * rows_per_part(cfg) * cfg["d"] * cfg["c"]
            * (cfg["r"] + 1))


def worker_bytes(cfg: dict) -> int:
    """The N dataset shares read once, the N weight shares and the N
    results."""
    n, d, c, r = cfg["N"], cfg["d"], cfg["c"], cfg["r"]
    elems = n * rows_per_part(cfg) * d + n * d * c * r + n * d * c
    return elems * field_bytes(cfg["p"])


def encode_ops(cfg: dict) -> int:
    """The round's weight encode: N shares, each a (K + T)-term combination
    of d c r elements."""
    return (2 * cfg["N"] * (cfg["K"] + cfg["T"]) * cfg["d"] * cfg["c"]
            * cfg["r"])


def decode_ops(cfg: dict) -> int:
    """K parts, each a threshold-term combination of d c elements."""
    return 2 * cfg["K"] * threshold(cfg) * cfg["d"] * cfg["c"]


def round_ops(cfg: dict) -> int:
    return worker_ops(cfg) + encode_ops(cfg) + decode_ops(cfg)


def load_peaks(path: pathlib.Path | None = None) -> dict:
    path = path or pathlib.Path(__file__).with_name("peaks.json")
    return json.loads(pathlib.Path(path).read_text())


def peaks_for(device_kind: str, table: dict | None = None) -> dict:
    """Peaks of one chip of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = table or load_peaks()
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])}); add it with its "
                       f"source") from None


def worker_roofline(cfg: dict, worker_s: float, chips: int,
                    peaks: dict) -> tuple[float, str]:
    """(share of the roofline in %, which bound applies) for all N workers
    of one round taking ``worker_s`` seconds on ``chips`` chips."""
    t_mem = worker_bytes(cfg) / (chips * peaks["hbm_bytes_per_s"])
    t_ops = worker_ops(cfg) / (chips * peaks["int8_ops_per_s"])
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_mem, t_ops) / worker_s, bound


def round_mfu(cfg: dict, rounds: int, window_s: float, chips: int,
              peaks: dict) -> float:
    """Field operations the rounds require, over the window and the chips'
    int8 peak, in %."""
    return (100.0 * round_ops(cfg) * rounds
            / (window_s * chips * peaks["int8_ops_per_s"]))
