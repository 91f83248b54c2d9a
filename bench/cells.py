"""Find a cell's pieces by name: nothing here names a cell, a configuration,
a traffic mix or a metric.

  BENCHMARK.json                      the cell, its configuration entry and
                                      the metrics it reports
  <config file>                       the sizes as run (configs/<name>.json)
  bench/traffic/<traffic>.json        the traffic mix, naming its driver
  bench/drivers/<driver>.py           the code that drives the program
  bench/metrics/<metric>.py           one reader per per-layer metric
  bench/references/<reference>.py     the plain reference a config names
  bench/limits/<workload>.json        the limits that decide ``correct``

A new configuration, traffic mix or per-layer metric is a new file and a
new entry in ``BENCHMARK.json``; no file that exists changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH_DIR = "bench"


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    workload: dict            # the BENCHMARK.json entry
    config: dict              # the configuration file's contents
    traffic: dict             # the traffic file's contents
    end_to_end: list[dict]    # end-to-end metrics this cell reports
    per_layer: list[dict]     # per-layer metrics this cell reports
    limits: dict              # number -> limit that decides ``correct``

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def driver(self) -> ModuleType:
        return load_module(self.root / BENCH_DIR / "drivers"
                           / f"{self.traffic['driver']}.py")

    def reference(self) -> ModuleType:
        return load_module(self.root / BENCH_DIR / "references"
                           / f"{self.config['reference']}.py")

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.root / BENCH_DIR / "metrics" / f"{name}.py")


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by its path, once per process."""
    name = "bench_piece_" + "_".join(
        part.replace("-", "_").replace(".", "_")
        for part in path.with_suffix("").parts[-2:])
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark piece: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _reports(metric: dict, workload: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads((root / BENCH_DIR / "traffic"
                          / f"{entry['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    limits_path = root / BENCH_DIR / "limits" / f"{workload}.json"
    limits = (json.loads(limits_path.read_text())["limits"]
              if limits_path.is_file() else {})
    return Cell(root=root, workload=entry, config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, limits=limits)
