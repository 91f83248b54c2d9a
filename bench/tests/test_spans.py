"""The readers of the program's own phases and scopes, on small synthetic
traces, and on the HLO text of a real compiled program."""
import jax
import jax.numpy as jnp
import pytest

from bench import measure, shapes, spans
from bench import trace as tr
from bench.metrics import (dataset_encode_ms, decode_ms, device_idle_share,
                           gather_ms, job_setup_ms, master_host_ms,
                           round_mfu, scope_decode_ms, scope_encode_ms,
                           scope_worker_ms, worker_ms, worker_roofline)

E = tr.Event
DEV = "/device:TPU:0"
SCOPES = {"jit__round": {"fusion.1": "jit(_round)/cpml_worker/dot_general",
                         "fusion.2": "jit(_round)/cpml_decode/add",
                         "add.3": "jit(_round)/cpml_encode_weights/add",
                         "copy.4": ""}}


def _measured(ops=None, modules=None, host=(), rounds=2, chips=1, lo=0,
              hi=1000):
    trace = tr.Trace(ops=ops or {}, modules=modules or {}, host=list(host))
    return measure.Measured(config={}, chips=chips, peaks={}, rounds=rounds,
                            window=trace, lo_ns=lo, hi_ns=hi, probes=None,
                            probe_calls={}, host_spans_s={})


def _round_ops(t0, dev_scale=1.0):
    """One run of jit__round at t0: encode 10, worker 100, decode 5, an
    unscoped copy 7 and a loop that only contains the others."""
    s = dev_scale
    return [E("%while.9 = (s32[]) while(%x)", t0, t0 + 130 * s),
            E("%add.3 = s32[8] add(%a, %b)", t0, t0 + 10 * s),
            E("%fusion.1 = s32[8] fusion(%a)", t0 + 10 * s, t0 + 110 * s),
            E("%copy.4 = s32[8] copy(%a)", t0 + 110 * s, t0 + 117 * s),
            E("%fusion.2 = s32[8] fusion(%c)", t0 + 117 * s, t0 + 122 * s)]


def test_scope_ms_sums_the_ops_under_a_scope_per_round_over_chips():
    ops = {DEV: _round_ops(100) + _round_ops(400),
           "/device:TPU:1": _round_ops(100, 2.0) + _round_ops(400, 2.0)}
    mods = {d: [E("jit__round(5)", 100, 400), E("jit__round(5)", 400, 700)]
            for d in ops}
    m = _measured(ops, mods, rounds=2, chips=2)
    # per round: 100 ns on chip 0, 200 ns on chip 1 -> 150 ns = 1.5e-4 ms
    assert spans.scope_ms(m, "cpml_worker", SCOPES) == pytest.approx(1.5e-4)
    assert spans.scope_ms(m, "cpml_encode_weights", SCOPES) == \
        pytest.approx(1.5e-5)
    assert spans.scope_ms(m, "cpml_decode", SCOPES) == pytest.approx(7.5e-6)
    # a scope name is a whole component of the op_name, not a substring
    assert spans.scope_ms(m, "cpml", SCOPES) is None


def test_scope_ms_clips_to_the_window_and_keeps_to_its_program():
    ops = {DEV: _round_ops(100) + [E("%fusion.1 = s32[8] fusion(%a)",
                                     800, 900)]}
    mods = {DEV: [E("jit__round(5)", 100, 400), E("jit_other(6)", 800, 900)]}
    # fusion.1 of another program is not the worker; the window cuts 60 ns
    m = _measured(ops, mods, rounds=1, hi=160)
    assert spans.scope_ms(m, "cpml_worker", SCOPES) == pytest.approx(5e-5)


def test_scope_readers_are_silent_without_the_scopes():
    ops = {DEV: _round_ops(100)}
    mods = {DEV: [E("jit__round(5)", 100, 400)]}
    m = _measured(ops, mods, rounds=1)
    bare = {"jit__round": {k: "jit(_round)/add" for k in SCOPES["jit__round"]}}
    for scope in ("cpml_worker", "cpml_encode_weights", "cpml_decode"):
        assert spans.scope_ms(m, scope, bare) is None
        assert spans.scope_ms(m, scope, {}) is None
    assert spans.scope_ms(_measured(rounds=1), "cpml_worker", SCOPES) is None
    # the readers themselves look the programs up in this process, which
    # holds no program called jit__round compiled with these instructions
    for reader in (scope_worker_ms, scope_encode_ms, scope_decode_ms):
        assert reader.read(m) is None


def test_op_names_come_from_the_compiled_hlo_text():
    def f(x):
        with jax.named_scope("cpml_worker"):
            y = jnp.sin(x) * 3
        with jax.named_scope("cpml_decode"):
            return jnp.cumsum(y) + 1

    x = jnp.arange(16.0)
    jitted = jax.jit(f)
    text = jitted.lower(x).compile().as_text()
    names = spans.op_names_from_hlo(text)
    assert any(spans.in_scope(v, "cpml_worker") for v in names.values())
    assert any(spans.in_scope(v, "cpml_decode") for v in names.values())
    jitted(x).block_until_ready()
    live = spans.live_op_names({"jit_f"})
    assert any(spans.in_scope(v, "cpml_decode")
               for v in live.get("jit_f", {}).values())
    assert spans.instruction("%fusion.3 = s32[2]{0} fusion(%a)") == "fusion.3"
    assert spans.instruction("dot_general.1") == "dot_general.1"


def _cluster_host():
    """Two rounds of the master: round r at 1000 r, its phases inside."""
    host = []
    for r in range(2):
        t = 1000 * r
        host += [E("bench_round", t, t + 900), E("cpml.round", t, t + 600),
                 E("cpml.fence", t, t + 50), E("cpml.dispatch", t + 60,
                                                t + 100),
                 E("cpml.collect", t + 100, t + 200),
                 E("cpml.decode_matrix", t + 200, t + 500),
                 E("cpml.round_program", t + 500, t + 560)]
        if r == 0:
            host.append(E("cpml.decode_solve", t + 210, t + 490))
    return host


def test_master_host_ms_reads_the_rounds_phases_and_idle():
    # the device runs each round's program from 550 to 850 after the round
    ops = {DEV: [E("%fusion.1 = s32[8] fusion(%a)", 1000 * r + 550,
                   1000 * r + 850) for r in range(2)]}
    m = _measured(ops, host=[E("bench_window", 0, 2000)] + _cluster_host(),
                  rounds=2, hi=2000)
    got = master_host_ms.read(m)
    assert got["value"] == pytest.approx(560e-6)
    assert got["rounds"] == 2
    assert got["decode_matrix_ms"] == pytest.approx(300e-6)
    assert got["scheduler_ms"] == pytest.approx(140e-6)
    assert got["fence_ms"] == pytest.approx(50e-6)
    assert got["solves_per_round"] == 0.5
    # idle inside bench_round: 0-550 and 850-900 each round; 50-60 (under
    # cpml.round alone) and 850-900 (after the round) are under no phase
    assert got["idle_ms"] == pytest.approx(600e-6)
    assert got["idle_unnamed_ms"] == pytest.approx(10e-6 + 50e-6)
    assert got["idle_decode_solve_ms"] == pytest.approx(280e-6 / 2)
    assert got["idle_decode_matrix_ms"] == pytest.approx(
        (300e-6 + 20e-6) / 2)
    assert got["idle_round_program_ms"] == pytest.approx(50e-6)
    assert got["idle_named_share"] == pytest.approx(100 * 540 / 600)


def test_master_host_ms_is_silent_without_the_programs_phases():
    host = [E("bench_window", 0, 2000), E("bench_round", 0, 900)]
    assert master_host_ms.read(_measured(host=host)) is None
    # rounds without a round program (a distributed master) read nothing
    host.append(E("cpml.round", 0, 600))
    assert master_host_ms.read(_measured(host=host)) is None


def test_job_setup_ms_reads_train_start_to_its_scan():
    host, mods = [], {DEV: [], "/device:TPU:1": []}
    for j in range(2):
        t = 10_000 * j
        host += [E("bench_job", t, t + 9000), E("cpml.train", t, t + 8900),
                 E("cpml.setup.encode_dataset", t + 100, t + 600),
                 E("cpml.setup.step_size", t + 600, t + 2600),
                 E("cpml.setup.schedule", t + 2600, t + 2700)]
        mods[DEV] += [E("jit_matmul(1)", t + 150, t + 500),
                      E("jit__train_scan(9)", t + 3000, t + 8800)]
        mods["/device:TPU:1"].append(E("jit__train_scan(9)", t + 3100,
                                       t + 8800))
    m = _measured(modules=mods, host=host, hi=20_000)
    got = job_setup_ms.read(m)
    assert got["value"] == pytest.approx(3050e-6)
    assert got["jobs"] == 2
    assert got["encode_dataset_ms"] == pytest.approx(500e-6)
    assert got["step_size_ms"] == pytest.approx(2000e-6)
    assert got["schedule_ms"] == pytest.approx(100e-6)
    # no job span, or no scan on a device: nothing to read
    assert job_setup_ms.read(_measured(modules=mods, host=host[:1],
                                       hi=20_000)) is None
    assert job_setup_ms.read(_measured(host=host, hi=20_000)) is None


def test_the_new_phases_leave_every_earlier_reading_unchanged():
    """The readers and reductions that were there read the same number on a
    trace whether or not the program's phases are in it."""
    ops = {DEV: _round_ops(100) + [E("%all-gather.1 = s32[4]", 300, 320)],
           "/device:TPU:1": _round_ops(100, 2.0)}
    mods = {DEV: [E("jit__round(5)", 100, 400),
                  E("jit_bench_worker_step(7)", 500, 600)],
            "/device:TPU:1": [E("jit_bench_worker_step(7)", 500, 700)]}
    base = [E("bench_window", 0, 1000), E("bench_round", 0, 900)]

    def measured(host):
        m = _measured(ops, mods, host=host, rounds=2, chips=2)
        m.probes = m.window
        m.probe_calls = {"bench_worker_step": 1, "bench_decode_step": 1}
        m.host_spans_s = {"dataset_encode": 0.5}
        m.config = {"N": 8, "K": 2, "T": 1, "r": 1, "c": 1, "m": 200,
                    "d": 16, "p": 16777213, "lx": 2, "lw": 4, "lc": 6}
        m.peaks = shapes.peaks_for("TPU v5 lite")
        return m

    before, after = measured(base), measured(base + _cluster_host())
    for reader in (dataset_encode_ms, decode_ms, device_idle_share,
                   gather_ms, round_mfu, worker_ms, worker_roofline):
        assert reader.read(after) == reader.read(before), reader.__name__
    assert tr.top_ops(after.window, 0, 1000) == tr.top_ops(before.window, 0,
                                                           1000)
    assert tr.busy_s(after.window, 0, 1000) == tr.busy_s(before.window, 0,
                                                         1000)
