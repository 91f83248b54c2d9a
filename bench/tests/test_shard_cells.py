"""The four-chip epsilon deployment, whose shares only four chips hold.
Found by name, run tiny on four CPU devices against the plain reference,
and read by the metrics that only it reports. (The binary scan jobs spread
over four chips are built by conftest and run in test_faults.py: their
configuration and traffic are the one-chip cell's, and BENCHMARK.json gives
a pair of configuration and traffic once.)"""
import json

import jax
import pytest

from conftest import REPO, TINY, run_tiny

from bench import cells, check, data, faults, measure, program, shapes, spans
from bench import trace as tr
from bench.metrics import scope_encode_dataset_ms, scope_worker_roofline
from bench.references import logistic as ref

EPSILON = "case1-epsilon-p30.scan-jobs.shard4"
E = tr.Event
DEV = "/device:TPU:0"


def _limit(workload):
    return cells.load_cell(REPO, workload).limits["w_rel_err"]


def test_the_four_chip_cell_names_its_pieces():
    cell = cells.load_cell(REPO, EPSILON)
    assert cell.chips == 4 and cell.traffic["driver"] == "scan_jobs"
    assert cell.config["reference"] == "logistic"
    assert {m["name"] for m in cell.per_layer} == {
        "gather_ms", "scope_encode_dataset_ms", "scope_worker_roofline"}
    assert {m["name"] for m in cell.end_to_end} == {"round_ms", "setup_s"}
    assert 0 < cell.limits["w_rel_err"] < 1e-3
    assert program.coded_config(cell.config, cell.chips).backend == "shard"


def test_each_pair_of_configuration_and_traffic_is_one_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [EPSILON]
    for metric in ("gather_ms", "scope_encode_dataset_ms",
                   "scope_worker_roofline"):
        entry = {m["name"]: m for m in bench["per_layer"]}[metric]
        assert entry["workloads"] == [EPSILON]


def test_the_epsilon_configuration_is_the_published_size_at_p30():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["cpml-case1-epsilon-p30"]
    config = json.loads((REPO / entry["file"]).read_text())
    assert entry["reduced"] == config["reduced"] == []
    assert (config["m"], config["d"], config["c"]) == (400000, 2000, 1)
    assert (config["N"], config["K"], config["T"], config["r"]) == \
        (40, 13, 1, 1)
    assert config["data"] == {"generator": "mnist_like", "sparsity": 0.0,
                              "margin": 10.0}
    cfg = program.coded_config(config, 4)
    assert cfg.headroom_bits(1.0, config["m"]) > 0
    # 10 of the 40 shares a chip: 2.46 GB
    assert cfg.N // 4 * shapes.rows_per_part(config) * config["d"] * 4 \
        == 2_461_600_000


def test_a_sound_epsilon_run_is_correct(tiny_root):
    res = run_tiny(tiny_root, EPSILON)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["w_rel_err"]["value"] == 0.0
    assert res["compilations_in_window"] == 0
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "exchange"])
def test_a_planted_fault_in_the_epsilon_cell_is_not_correct(tiny_root,
                                                            fault):
    with faults.planted(fault):
        res = run_tiny(tiny_root, EPSILON)
    assert res["correct"] is False, res["checks"]


def test_the_reference_follows_protocol_train_on_dense_p30_rows():
    config = json.loads((REPO / "bench" / "configs"
                         / "cpml-case1-epsilon-p30.json").read_text())
    config.update(TINY)
    x, y = data.make_dataset(config, 2**35 + 7)
    cfg = program.coded_config(config, 1)
    for j in range(2):
        key = jax.random.fold_in(data.stream(11, data.JOBS), j)
        w, _ = program.protocol.train(cfg, key, x, y, 20)
        assert check.rel_err(w, ref.train(config, x, y, key, 20)) == 0.0
    w_ref = ref.train(config, x, y, key, 50)
    w_ctrl = ref.train(config, x, y, key, 50, ref.BF16)
    assert check.rel_err(w_ctrl, w_ref) > _limit(EPSILON)


# the readers, on a synthetic trace of two jobs of two rounds on two chips
NAMES = {"jit__train_scan": {"fusion.1": "jit(_train_scan)/cpml_worker/dot",
                             "all-gather.2": "jit(_train_scan)/cpml_worker/"
                                             "all_gather"},
         "jit__encode_block": {"fusion.3": "jit(_encode_block)/"
                                           "cpml_encode_dataset/dot"}}


def _two_jobs(monkeypatch, scale=1.0):
    ops, mods = {}, {}
    for c, dev in enumerate((DEV, "/device:TPU:1")):
        s = scale * (1 + c)              # chip 1 twice as slow
        ops[dev], mods[dev] = [], []
        for job in range(2):
            t = 10_000 * job
            mods[dev] += [E("jit__encode_block(3)", t, t + 1000),
                          E("jit__train_scan(4)", t + 2000, t + 9000)]
            ops[dev] += [E("%fusion.3 = s32[8] fusion(%a)", t, t + 300 * s)]
            for r in range(2):
                u = t + 2000 + 3000 * r
                ops[dev] += [E("%fusion.1 = s32[8] fusion(%a)", u,
                               u + 1000 * s),
                             E("%all-gather.2 = s32[8] all-gather(%a)",
                               u + 1000 * s, u + 1100 * s)]
    host = [E("cpml.train", 10_000 * j, 10_000 * j + 9500) for j in range(2)]
    monkeypatch.setattr(spans, "live_op_names", lambda programs: NAMES)
    config = json.loads((REPO / "bench" / "configs"
                         / "cpml-case1-mnist37.json").read_text())
    return measure.Measured(
        config=config, chips=2, peaks=shapes.peaks_for("TPU v5 lite"),
        rounds=4, window=tr.Trace(ops=ops, modules=mods, host=host),
        lo_ns=0, hi_ns=20_000, probes=None, probe_calls={}, host_spans_s={})


def test_scope_encode_dataset_ms_is_device_ms_a_job(monkeypatch):
    m = _two_jobs(monkeypatch)
    # 300 ns on chip 0, 600 ns on chip 1, each job -> 450 ns = 4.5e-4 ms
    assert scope_encode_dataset_ms.read(m) == pytest.approx(4.5e-4)
    m.window.host = []
    assert scope_encode_dataset_ms.read(m) is None


def test_scope_worker_roofline_reads_the_worker_scope(monkeypatch):
    m = _two_jobs(monkeypatch)
    got = scope_worker_roofline.read(m)
    # the worker scope holds its all-gather: 1100 / 2200 ns a round
    share, bound = shapes.worker_roofline(m.config, 1650e-9, 2, m.peaks)
    assert got == {"value": pytest.approx(share), "bound": bound}
    monkeypatch.setattr(spans, "live_op_names", lambda programs: {})
    assert scope_worker_roofline.read(m) is None
