"""A copy of the benchmark at a size the CPU runs in seconds.

``tiny_root`` holds BENCHMARK.json with the real cells, whose configuration
files keep every key and shrink the scale (N=8, K=2, T=1, m=200, d=16), the
benchmark's own files, and a link to the program. Runs from it skip the
look for a chip.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
TINY = {"N": 8, "K": 2, "T": 1, "m": 200, "d": 16}
SHARD = "case1-mnist37.scan-jobs.shard4"

# the shard cell needs four devices; the flag only acts before JAX starts
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    dest = pathlib.Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (dest / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = dest / c["file"]
        config = json.loads(path.read_text())
        config.update(TINY)
        path.write_text(json.dumps(config))
    # the four-chip cell is not in BENCHMARK.json until it is measured on a
    # 2x2 host (PERF.md §7); its pieces are, and it is built from them here
    if SHARD not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append(
            {"name": SHARD, "config": "cpml-case1-mnist37",
             "traffic": "scan-jobs", "chips": 4, "why": "shard backend"})
        bench["per_layer"].append(
            {"name": "gather_ms", "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "collective",
             "moves": "round_ms", "workloads": [SHARD]})
        (dest / "bench" / "limits" / f"{SHARD}.json").write_text(
            (dest / "bench" / "limits"
             / "case1-mnist37.scan-jobs.json").read_text())
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


def run_tiny(root, workload: str, seed: int = 3, seconds: float = 0.5,
             traced: bool = False, **kw) -> dict:
    from bench import cells, run
    import time
    cell = cells.load_cell(root, workload)
    return run.run_cell(cell, seed, seconds, traced, require_tpu=False,
                        t_start=time.perf_counter(),
                        peaks_kind="TPU v5 lite", **kw)
