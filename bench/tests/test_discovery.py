"""A cell is found by name: a configuration, a traffic mix or a per-layer
metric is added by new files and entries alone."""
import json
import subprocess
import sys

import pytest

from conftest import REPO, make_tiny_root, run_tiny

from bench import cells


def test_the_real_benchmark_file_names_pieces_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load_cell(REPO, w["name"])
        assert cell.driver().setup and cell.driver().window
        assert cell.reference().train
        for m in cell.per_layer:
            assert cell.metric_reader(m["name"]).read
        assert {m["name"] for m in cell.end_to_end} >= {"round_ms", "setup_s"}
        assert cell.per_layer, w["name"]
        assert "use_kernel" not in cell.config and "backend" not in cell.config


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    root = make_tiny_root(tmp_path)
    bench_dir = root / "bench"
    # a new configuration: a file of sizes, naming the existing reference
    config = json.loads((bench_dir / "configs" / "cpml-case1-mnist37.json")
                        .read_text())
    config.update(name="cpml-new", r=2, N=12, p=1073741789, m=150)
    (bench_dir / "configs" / "cpml-new.json").write_text(json.dumps(config))
    # a new traffic mix: a data file for an existing driver
    (bench_dir / "traffic" / "short-jobs.json").write_text(json.dumps(
        {"driver": "scan_jobs", "iters": 3, "warmup_jobs": 1}))
    # a new per-layer metric: a reader of its own
    (bench_dir / "metrics" / "rounds_traced.py").write_text(
        "def read(m):\n    return float(m.rounds)\n")
    (bench_dir / "limits" / "new.short.json").write_text(json.dumps(
        {"limits": {"w_rel_err": 1e-5}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "cpml-new", "source": "x",
                             "file": "bench/configs/cpml-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.short", "config": "cpml-new",
                               "traffic": "short-jobs", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "job / round driver",
                               "moves": "round_ms",
                               "workloads": ["new.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell(root, "new.short")
    assert cell.config["r"] == 2 and cell.traffic["iters"] == 3
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced"]
    res = run_tiny(root, "new.short", traced=True)
    assert res["correct"] is True
    assert res["metrics"]["rounds_traced"]["value"] == res["attempted"]
    assert res["attempted"] % 3 == 0


def test_a_cell_names_no_implementation_switch():
    from bench import program
    config = json.loads((REPO / "bench" / "configs" / "cpml-case1-mnist37.json")
                        .read_text())
    assert program.coded_config(config, 4).backend == "shard"
    assert program.coded_config(
        config, 1, {"protocol": {"batch_rows": 64}}).batch_rows == 64
    for bad in ({"use_kernel": True}, {"backend": "shard"}, {"nope": 1}):
        with pytest.raises(ValueError, match="protocol setting"):
            program.coded_config(config, 1, {"protocol": bad})
    with pytest.raises(ValueError, match="protocol setting"):
        program.coded_config(dict(config, use_kernel=True), 1)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result(tmp_path):
    for root in (REPO, make_tiny_root(tmp_path)):
        proc = subprocess.run(
            [sys.executable, str(root / "bench" / "run.py"), "--workload",
             "case1-mnist37.scan-jobs", "--seed", "1", "--seconds", "1",
             "--trace", "0"], capture_output=True, text=True, timeout=300,
            env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
        assert proc.returncode == 3, proc.stderr
        assert proc.stdout.strip() == ""
        assert "no TPU" in proc.stderr
