"""The reduction from a trace to metrics, on small synthetic traces."""
import pytest

from bench import measure
from bench import trace as tr
from bench.metrics import device_idle_share, gather_ms, worker_ms

E = tr.Event


def test_union_merges_overlaps_and_clips_to_the_window():
    evs = [E("a", 0, 10), E("b", 5, 15), E("c", 20, 30), E("d", 28, 40),
           E("e", 50, 60)]
    assert tr.union_ns(evs, 0, 100) == 15 + 20 + 10
    assert tr.union_ns(evs, 8, 55) == 7 + 20 + 5
    assert tr.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_of_the_union():
    evs = [E("a", 10, 20), E("b", 15, 30), E("c", 40, 45)]
    assert tr.gaps(evs, 0, 50) == [(0, 10), (30, 40), (45, 50)]
    busy = tr.union_ns(evs, 0, 50)
    assert busy + sum(b - a for a, b in tr.gaps(evs, 0, 50)) == 50


def test_sum_by_name_and_matching():
    evs = [E("%all-gather.1 = s32[4]", 0, 3), E("%all-gather.1 = s32[4]", 10, 14),
           E("%add.2 = s32[4]", 3, 4)]
    sums = tr.sum_by_name(evs, 0, 100)
    assert sums["%all-gather.1 = s32[4]"] == pytest.approx(7e-9)
    assert len(tr.matching(evs, "ALL-GATHER")) == 2


def test_opcode_reads_tuple_shapes_and_top_ops_skip_loops():
    assert tr.opcode("%while.8 = (s32[]{:T(128)}, f32[3]) while((s32[]) %t)") \
        == "while"
    assert tr.opcode("%fusion.9 = s32[40,954,2]{1,2,0} fusion(s32[40] %x)") \
        == "fusion"
    assert tr.opcode("jit_add(123)") == ""
    trace = tr.Trace(ops={"/device:TPU:0": [
        E("%while.1 = (s32[]) while(%x)", 0, 100),
        E("%fusion.1 = s32[2] fusion(%a)", 0, 30),
        E("%add.1 = s32[2] add(%a)", 40, 50)]}, modules={}, host=[])
    assert [n for n, _ in tr.top_ops(trace, 0, 100)] == [
        "%fusion.1 = s32[2] fusion(%a)", "%add.1 = s32[2] add(%a)"]


def test_idle_gaps_are_named_by_host_activity_and_next_program():
    dev = "/device:TPU:0"
    trace = tr.Trace(
        ops={dev: [E("%a", 0, 10), E("%b", 30, 40), E("%c", 45, 50)]},
        modules={dev: [E("jit_x(1)", 0, 10), E("jit_y(2)", 30, 40),
                       E("jit_y(2)", 45, 50)]},
        host=[E("bench_window", 0, 100), E("bench_job", 0, 100),
              E("Linearize", 12, 28)])
    got = dict(tr.idle_gaps(trace, 0, 60))
    assert got["bench_window > bench_job > Linearize -> jit_y"] == \
        pytest.approx(20e-9)
    assert got["bench_window > bench_job -> jit_y"] == pytest.approx(5e-9)
    assert got["bench_window > bench_job -> window end"] == \
        pytest.approx(10e-9)


def _measured(ops, modules=None, rounds=10, chips=1, lo=0, hi=100):
    trace = tr.Trace(ops=ops, modules=modules or {}, host=[])
    return measure.Measured(config={}, chips=chips, peaks={}, rounds=rounds,
                            window=trace, lo_ns=lo, hi_ns=hi, probes=trace,
                            probe_calls={"bench_worker_step": 2},
                            host_spans_s={})


def test_idle_share_is_the_mean_over_chips():
    m = _measured({"/device:TPU:0": [E("%a", 0, 50)],
                   "/device:TPU:1": [E("%a", 0, 25), E("%b", 20, 30)]},
                  chips=2)
    assert m.window_s == pytest.approx(100e-9)
    assert device_idle_share.read(m) == pytest.approx(100 * (1 - 40 / 100))
    assert device_idle_share.read(_measured({})) is None


def test_gather_ms_per_round_and_silent_on_one_chip():
    ops = {"/device:TPU:0": [E("%all-gather-start.1 = s32[40]", 0, 2e6)],
           "/device:TPU:1": [E("%all-gather-start.1 = s32[40]", 0, 4e6)]}
    m = _measured(ops, rounds=2, chips=2, hi=1e9)
    assert gather_ms.read(m) == pytest.approx(1.5)
    assert gather_ms.read(_measured(ops, rounds=2, chips=1, hi=1e9)) is None
    assert gather_ms.read(_measured({"/device:TPU:0": [E("%add", 0, 5)]},
                                    chips=2)) is None


def test_probe_ms_is_the_mean_run_over_devices():
    mods = {"/device:TPU:0": [E("jit_bench_worker_step(7)", 0, 2e6),
                              E("jit_bench_worker_step(7)", 5e6, 9e6),
                              E("jit_other(1)", 0, 50e6)],
            "/device:TPU:1": [E("jit_bench_worker_step(7)", 0, 6e6)]}
    m = _measured({}, modules=mods)
    assert worker_ms.read(m) == pytest.approx((3 + 6) / 2)
    m.probe_calls = {}
    assert worker_ms.read(m) is None
