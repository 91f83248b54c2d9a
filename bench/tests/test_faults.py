"""A whole run with the timed path broken underneath reads ``correct``
false, once for each fault a cell can have; a sound run reads true.

Runs skip the look for a chip; the shard cell runs on four CPU devices.
"""
import pytest

from conftest import run_tiny

from bench import faults

ONE_CHIP = ["case1-mnist37.scan-jobs", "case1-mnist37.cluster-lognormal",
            "case1-mnist10-p30.scan-jobs"]
SHARD = "case1-mnist37.scan-jobs.shard4"


@pytest.mark.parametrize("workload", ONE_CHIP + [SHARD])
def test_a_sound_run_is_correct(tiny_root, workload):
    res = run_tiny(tiny_root, workload)
    assert res["correct"] is True, res["checks"]
    assert res["compilations_in_window"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", ONE_CHIP + [SHARD])
def test_a_planted_fault_is_not_correct(tiny_root, workload, fault):
    with faults.planted(fault):
        res = run_tiny(tiny_root, workload)
    assert res["correct"] is False, res["checks"]


def test_the_exchange_between_chips_left_out_is_not_correct(tiny_root):
    with faults.planted("exchange"):
        res = run_tiny(tiny_root, SHARD)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["w_rel_err"]["value"] > 0.1


def test_a_traced_run_reads_the_probes_by_name(tiny_root):
    res = run_tiny(tiny_root, "case1-mnist37.scan-jobs", traced=True)
    assert res["correct"] is True
    assert "dataset_encode_ms" in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
