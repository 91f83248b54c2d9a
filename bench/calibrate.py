"""The readings each limit of ``correct`` is set from, taken on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,...,12 --control-seeds 21,22,23 [--faults half,altered]

All in one process, at the cell's own size:

  sound    a run of the cell as ``bench/run.py`` makes it, with a window of
           ``--seconds``, for every seed: the largest reading is the lower
  control  for every control seed, the program's answers recomputed by the
           plain reference with bfloat16 operands (one MXU pass, the step
           below the float32 the reference states) and compared with the
           reference at full precision, as the program's are
  faults   for every fault named (``bench/faults.py``) and control seed, a
           run with the fault planted in the program

A state left unchanged reads 1 by this measure and needs no run. Prints one
JSON line with every reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells, run  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def reading(result: dict) -> float:
    return result["checks"]["w_rel_err"]["value"]


def calibrate(cell: cells.Cell, seeds, control_seeds, faults, seconds: float,
              require_tpu: bool = True, peaks_kind: str | None = None
              ) -> dict:
    from bench import check
    kw = dict(require_tpu=require_tpu, peaks_kind=peaks_kind)
    out = {"workload": cell.name, "sound": {}, "control": {}, "faults": {}}
    for name in faults:
        from bench import faults as planted
        out["faults"][name] = {}
        with planted.planted(name):
            for seed in control_seeds:
                res = run.run_cell(cell, seed, seconds, False,
                                   t_start=time.perf_counter(), **kw)
                out["faults"][name][seed] = reading(res)
                print(f"fault {name} seed {seed}: {reading(res)!r}",
                      file=sys.stderr, flush=True)
    reference = cell.reference()
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        keep = {}
        res = run.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(), keep=keep, **kw)
        if seed in seeds:
            out["sound"][seed] = reading(res)
            print(f"sound seed {seed}: {reading(res)!r} "
                  f"({res['attempted']} rounds)", file=sys.stderr, flush=True)
        if seed in control_seeds:
            ctrl = [(k, r, reference.train(cell.config, keep["x"], keep["y"],
                                           k, r, reference.BF16))
                    for k, r, _ in keep["answers"]]
            got = check.readings(reference, cell.config, keep["x"],
                                 keep["y"], ctrl)["w_rel_err"]
            out["control"][seed] = got
            print(f"control seed {seed}: {got!r}", file=sys.stderr,
                  flush=True)
    out["faults"]["unchanged"] = "1 by the measure: w stays 0"
    out["lower"] = max(out["sound"].values()) if out["sound"] else None
    out["control_min"] = (min(out["control"].values())
                          if out["control"] else None)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="",
                    help="comma-separated names from bench/faults.py")
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    out = calibrate(cell, args.seeds, args.control_seeds,
                    [f for f in args.faults.split(",") if f], args.seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
