"""Work and bytes counted from the algorithm's shapes, against hand counts,
and the table of peaks."""
import json
import pathlib

import pytest

from bench import shapes

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_case1_binary_counts():
    cfg = config("cpml-case1-mnist37")
    assert shapes.field_bytes(cfg["p"]) == 3            # 24-bit prime
    assert shapes.rows_per_part(cfg) == 954             # ceil(12396 / 13)
    assert shapes.threshold(cfg) == 40
    # 2 N (m/K) d c (r+1) = 2 * 40 * 954 * 1568 * 1 * 2
    assert shapes.worker_ops(cfg) == 239_339_520
    # 3 bytes * (40*954*1568 shares + 40*1568 weight shares + 40*1568 results)
    assert shapes.worker_bytes(cfg) == 3 * (59_834_880 + 62_720 + 62_720)
    assert shapes.encode_ops(cfg) == 2 * 40 * 14 * 1568
    assert shapes.decode_ops(cfg) == 2 * 13 * 40 * 1568
    assert shapes.round_ops(cfg) == 239_339_520 + 1_756_160 + 1_630_720


def test_case1_ten_class_p30_counts():
    cfg = config("cpml-case1-mnist10-p30")
    assert shapes.field_bytes(cfg["p"]) == 4            # 30-bit prime
    assert shapes.rows_per_part(cfg) == 4616            # ceil(60000 / 13)
    assert shapes.worker_ops(cfg) == 2 * 40 * 4616 * 784 * 10 * 2
    assert shapes.worker_bytes(cfg) == 4 * (40 * 4616 * 784 + 40 * 784 * 10
                                            + 40 * 784 * 10)
    assert shapes.decode_ops(cfg) == 2 * 13 * 40 * 784 * 10


def test_roofline_names_its_bound_and_scales_with_chips():
    cfg = config("cpml-case1-mnist37")
    peaks = shapes.peaks_for("TPU v5 lite")
    least = shapes.worker_bytes(cfg) / peaks["hbm_bytes_per_s"]
    share, bound = shapes.worker_roofline(cfg, 10 * least, 1, peaks)
    assert bound == "memory" and share == pytest.approx(10.0)
    share4, _ = shapes.worker_roofline(cfg, 10 * least / 4, 4, peaks)
    assert share4 == pytest.approx(10.0)
    fast = dict(peaks, hbm_bytes_per_s=1e30)
    assert shapes.worker_roofline(cfg, 1.0, 1, fast)[1] == "compute"


def test_round_mfu():
    cfg = config("cpml-case1-mnist37")
    peaks = shapes.peaks_for("TPU v5 lite")
    got = shapes.round_mfu(cfg, rounds=100, window_s=2.0, chips=1,
                           peaks=peaks)
    assert got == pytest.approx(100 * shapes.round_ops(cfg) * 100 / 2.0
                                / 393e12)


def test_peaks_table_has_its_source_and_refuses_an_unknown_device():
    table = shapes.load_peaks()
    assert "TPU v5e" in table["source"]
    v5e = shapes.peaks_for("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        shapes.peaks_for("cpu")
