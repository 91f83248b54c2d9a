"""Flight recorder acceptance (DESIGN.md §11, repro.obs).

Fast tests cover the recorder's invariants (every span closes, spans nest,
foreign-process spans never mix into master stacks), the metrics registry
and its Prometheus text format, the zeroed empty-run wait_summary contract,
and — on the simulated backend — that a traced run is bit-identical to an
untraced one while producing a Perfetto-valid trace whose per-round
critical-path sums reconcile exactly with wait_stats.

Slow tests put the same invariants on real infrastructure: a socket run
must produce the SAME span structure as a simulated run (same names, same
nesting — only the numbers differ), worker-side spans must arrive over the
v2 TRACE wire field, and a forced-v1 fleet must round-trip with worker
traces silently absent.
"""
import json
import math
import warnings

import jax
import numpy as np
import pytest

from repro.cluster import ClusterRunner, make_latency
from repro.cluster.runner import wait_summary
from repro.core import protocol
from repro.data import synthetic
from repro.obs.export import (round_summaries, straggler_report,
                              to_chrome_trace, validate_chrome_trace,
                              waterfall)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_RECORDER, NullRecorder, Recorder, structure


# ---------------------------------------------------------------------------
# Recorder invariants
# ---------------------------------------------------------------------------

def test_spans_nest_and_close():
    clock = iter(float(i) for i in range(100))
    rec = Recorder(clock_fn=lambda: next(clock))
    outer = rec.begin("round", round=0)
    with rec.span("collect", round=0):
        rec.instant("fold", worker=3)
    rec.end(outer)
    assert not rec.open_spans()
    collect = rec.find("collect")[0]
    assert collect.parent == "round"
    assert rec.find("fold")[0].parent == "collect"
    assert rec.find("round")[0].parent is None
    assert collect.duration > 0


def test_exception_unwind_closes_children():
    rec = Recorder()
    outer = rec.begin("round")
    inner = rec.begin("collect")        # never explicitly ended: an
    rec.end(outer)                      # exception unwound past it
    assert not inner.open
    assert inner.end == outer.end
    assert not rec.open_spans()


def test_tracks_have_independent_stacks():
    rec = Recorder()
    with rec.span("round"):
        with rec.span("prefetch_build", track="prefetch"):
            pass
    build = rec.find("prefetch_build")[0]
    assert build.parent is None          # different track: no nesting
    assert build.track == "prefetch"


def test_add_process_spans_stays_in_foreign_clock_domain():
    rec = Recorder()
    with rec.span("round", round=2):
        rec.add_process_spans("worker3",
                              [["recv", 0.1, 0.2], ["compute", 0.2, 0.9]],
                              round=2)
    w = [s for s in rec.spans if s.process == "worker3"]
    assert [s.name for s in w] == ["recv", "compute"]
    # foreign spans never nest under master spans (different clock epoch)
    assert all(s.parent is None for s in w)
    assert all(s.args == {"round": 2} for s in w)


def test_add_process_spans_drops_malformed_triples():
    rec = Recorder()
    rec.add_process_spans("worker0",
                          [["ok", 1.0, 2.0], ["short"], "junk", None,
                           ["bad", "x", 3.0], ["also_ok", 3, 4]])
    assert [s.name for s in rec.spans] == ["ok", "also_ok"]


def test_null_recorder_is_inert():
    n = NullRecorder()
    assert not n.enabled and NULL_RECORDER.enabled is False
    with n.span("anything", round=1) as s:
        assert s is None
    n.end(n.begin("x"))
    n.instant("y")
    n.add_span("z", 0.0, 1.0)
    n.add_process_spans("w", [["a", 0, 1]])
    assert n.spans == () and n.open_spans() == [] and n.find("x") == []
    # the context manager is a shared singleton: zero per-call allocation
    assert n.span("a") is n.span("b")


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("rounds_total", "rounds")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)                        # counters are monotone
    g = m.gauge("alive", "workers")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    h = m.histogram("wait_seconds", "waits")
    h.observe(0.05)
    h.observe(math.nan)                  # skipped, never poisons the sum
    h.observe(math.inf)                  # counted, excluded from the sum
    assert h.count == 2
    assert h.sum == pytest.approx(0.05)


def test_registry_get_or_create_and_kind_mismatch():
    m = MetricsRegistry()
    assert m.counter("a", "x") is m.counter("a", "x")
    with pytest.raises(TypeError):
        m.gauge("a", "x")


def test_snapshot_and_prometheus_format():
    m = MetricsRegistry()
    m.counter("cpml_rounds_total", "completed rounds").inc(3)
    m.gauge("cpml_workers_alive", "alive").set(8)
    h = m.histogram("cpml_wait_seconds", "waits", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = m.snapshot()
    json.dumps(snap)                     # JSON-able by construction
    assert snap["cpml_rounds_total"]["value"] == 3
    text = m.to_prometheus()
    assert "# TYPE cpml_rounds_total counter" in text
    assert "cpml_rounds_total 3" in text
    assert "cpml_workers_alive 8" in text
    # cumulative buckets + the +Inf catch-all
    assert 'cpml_wait_seconds_bucket{le="0.1"} 1' in text
    assert 'cpml_wait_seconds_bucket{le="1"} 2' in text
    assert 'cpml_wait_seconds_bucket{le="+Inf"} 3' in text
    assert "cpml_wait_seconds_count 3" in text


def test_metrics_write_json_vs_prom(tmp_path):
    m = MetricsRegistry()
    m.counter("c_total", "c").inc()
    jp, pp = tmp_path / "m.json", tmp_path / "m.prom"
    m.write(str(jp))
    m.write(str(pp))
    assert json.loads(jp.read_text())["c_total"]["value"] == 1
    assert "# TYPE c_total counter" in pp.read_text()


# ---------------------------------------------------------------------------
# Empty-run wait stats: the zeroed-summary contract (satellite fix)
# ---------------------------------------------------------------------------

def test_wait_summary_empty_is_zeroed_and_warning_free():
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # numpy mean-of-empty would warn
        s = wait_summary([])
    assert s == {"mean": 0.0, "p50": 0.0, "p95": 0.0, "total": 0.0}


def test_wait_stats_on_runner_with_no_rounds():
    x, y = synthetic.mnist_like(jax.random.PRNGKey(42), m=64, d=8)
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                           make_latency("deterministic"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        stats = runner.wait_stats()      # zero completed rounds
    for key in ("coded_T", "wait_all", "encode", "decode", "critical_path"):
        assert stats[key] == {"mean": 0.0, "p50": 0.0, "p95": 0.0,
                              "total": 0.0}
    assert stats["rounds"]["n"] == 0.0
    json.dumps(stats)                    # finite + serializable throughout


# ---------------------------------------------------------------------------
# Traced simulated runs: invariants + reconciliation + bit-identity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim_data():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=128, d=16)


def _sim_run(x, y, recorder=None, **kw):
    cfg = protocol.CPMLConfig(N=6, K=1, T=1, r=1)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                           make_latency("lognormal", seed=3),
                           encode_cost_s=0.02, decode_cost_s=0.01,
                           recorder=recorder, **kw)
    w = runner.run(5)
    return runner, w


def test_traced_sim_run_invariants(sim_data):
    x, y = sim_data
    rec = Recorder()
    runner, w = _sim_run(x, y, recorder=rec)
    assert runner.obs is rec
    assert not rec.open_spans()          # every span closed
    names = {s.name for s in rec.spans}
    assert {"round", "dispatch", "collect", "encode", "wait",
            "decode", "flight"} <= names
    # derived spans nest under their round
    for nm in ("encode", "wait", "decode"):
        assert all(s.parent == "round" for s in rec.find(nm))
    # one flight lane per responding worker, parented to nothing (they live
    # on per-worker tracks) and stamped with the worker index
    for s in rec.find("flight"):
        assert s.track == f"worker/{s.args['worker']}"
        assert s.duration >= 0


def test_traced_sim_bit_identical_to_untraced(sim_data):
    x, y = sim_data
    _, w_off = _sim_run(x, y, recorder=None)
    _, w_on = _sim_run(x, y, recorder=Recorder())
    assert (np.asarray(w_off) == np.asarray(w_on)).all()


def test_chrome_trace_export_is_valid_and_reconciles(sim_data):
    x, y = sim_data
    rec = Recorder()
    runner, _ = _sim_run(x, y, recorder=rec)
    obj = to_chrome_trace(rec)
    assert validate_chrome_trace(obj) == []
    # the reconciliation surface: per-round critical-path components read
    # back from the SPANS must equal what wait_stats aggregated from the
    # RoundTraces (same numbers, same clock)
    rows = round_summaries(rec)
    assert [r["round"] for r in rows] == list(range(5))
    stats = runner.wait_stats()
    assert sum(r["critical_path"] for r in rows) == pytest.approx(
        stats["critical_path"]["total"], rel=1e-9)
    assert sum(r["wait"] for r in rows) == pytest.approx(
        stats["coded_T"]["total"], rel=1e-9)
    assert "round" in waterfall(rec)     # terminal view renders


def test_straggler_report_attributes_decisive_waits(sim_data):
    x, y = sim_data
    runner, _ = _sim_run(x, y, recorder=Recorder())
    text, stats = straggler_report(runner.traces, runner.cfg.threshold)
    assert "straggler attribution" in text
    assert set(stats) == set(range(runner.cfg.N))
    # exactly one decisive (threshold-th) arrival per completed round
    assert sum(s["decisive"] for s in stats.values()) == len(runner.traces)
    assert all(s["marginal_wait_s"] >= 0 for s in stats.values())


def test_metrics_populated_by_sim_run(sim_data):
    x, y = sim_data
    runner, _ = _sim_run(x, y, recorder=Recorder())
    snap = runner.metrics.snapshot()
    assert snap["cpml_rounds_total"]["value"] == 5
    assert snap["cpml_round_wait_seconds"]["count"] == 5
    assert snap["cpml_round_wait_seconds"]["sum"] == pytest.approx(
        runner.wait_stats()["coded_T"]["total"], rel=1e-9)
    assert snap["cpml_workers_alive"]["value"] == 6


def test_round_record_is_thin_view_over_trace(sim_data):
    x, y = sim_data
    runner, _ = _sim_run(x, y, recorder=None)
    for t, rec in runner.records.items():
        tr = runner.traces[t]
        assert rec.trace is tr
        assert rec.coded_wait_s == tr.coded_wait_s
        assert rec.encode_s == tr.encode_s
        assert rec.n_responders == len(tr.responders)
        assert (rec.dispatched == tr.dispatched).all()


def test_mpc_sim_run_traces_barriers():
    from repro.cluster.mpc_runner import MPCClusterRunner, mpc_phase_models
    from repro.core import mpc_baseline
    x, y = synthetic.mnist_like(jax.random.PRNGKey(42), m=64, d=8)
    cfg = mpc_baseline.MPCConfig(N=5, T=1, r=2)
    rec = Recorder()
    runner = MPCClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                              mpc_phase_models("lognormal", r=cfg.r),
                              recorder=rec)
    runner.run(3)
    assert not rec.open_spans()
    names = {s.name for s in rec.spans}
    assert {"mpc_round", "dispatch", "collect", "wait", "barrier",
            "flight"} <= names
    # r reshare barriers per round, chained on the master timeline
    assert len(rec.find("barrier")) == 3 * cfg.r
    assert validate_chrome_trace(to_chrome_trace(rec)) == []
    assert runner.metrics.snapshot()["mpc_rounds_total"]["value"] == 3


# ---------------------------------------------------------------------------
# Cross-backend structure + v1-wire degradation (real processes: slow)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def socket_data():
    return synthetic.mnist_like(jax.random.PRNGKey(42), m=256, d=20)


def _socket_run(x, y, *, wire_version=2, recorder=None, sleep_s=None):
    from repro.launch.cpml_cluster import local_socket_cluster
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)        # threshold 4
    with local_socket_cluster(cfg.N, wire_version=wire_version,
                              sleep_s=sleep_s) as tr:
        runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                               latency=None, transport=tr,
                               round_timeout_s=120.0, recorder=recorder)
        runner.provision()
        w = runner.run(5)
        runner.shutdown_workers()
    return runner, w


@pytest.mark.slow
def test_sim_and_socket_traces_share_structure(socket_data):
    """The pluggable-clock contract: SimClock and WallClock runs go through
    the same instrumented call sites, so the master-side span structure
    (names + nesting, per structure()'s track-collapsed view) is identical
    — only provisioning (meaningless in-process) is socket-only."""
    x, y = socket_data
    cfg = protocol.CPMLConfig(N=5, K=1, T=1, r=1)
    sim_rec = Recorder()
    sim = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                        make_latency("deterministic"),
                        encode_cost_s=0.01, decode_cost_s=0.01,
                        recorder=sim_rec)
    sim.run(5)
    sock_rec = Recorder()
    _socket_run(x, y, recorder=sock_rec)
    sim_shape = structure(sim_rec)
    sock_shape = structure(sock_rec)
    assert sock_shape - {("master", "provision", None)} == sim_shape
    assert not sock_rec.open_spans()


@pytest.mark.slow
def test_socket_worker_spans_arrive_over_v2_wire(socket_data):
    x, y = socket_data
    rec = Recorder()
    runner, w = _socket_run(x, y, wire_version=2, recorder=rec)
    worker_procs = {s.process for s in rec.spans
                    if s.process.startswith("worker")}
    assert worker_procs                   # at least one worker shipped spans
    for p in worker_procs:
        names = {s.name for s in rec.spans if s.process == p}
        assert {"recv", "compute", "serialize"} <= names
    # warm-compile (measured in the provisioning window) reached the gauge
    snap = runner.metrics.snapshot()
    assert snap["cpml_xla_warm_compile_seconds"]["value"] > 0
    # and the export is Perfetto-valid with multiple processes
    obj = to_chrome_trace(rec)
    assert validate_chrome_trace(obj) == []
    pids = {e["pid"] for e in obj["traceEvents"]}
    assert len(pids) >= 2
    # reconciliation holds on the wall clock too
    assert sum(r["critical_path"] for r in round_summaries(rec)) == \
        pytest.approx(runner.wait_stats()["critical_path"]["total"],
                      rel=1e-9)
    # and tracing changed no bit of the training
    w_ref, _ = protocol.train_reference(
        runner.cfg, jax.random.PRNGKey(7), x, y, iters=5,
        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()


@pytest.mark.slow
def test_v1_fleet_roundtrips_with_traces_silently_absent(socket_data):
    """A forced-v1 fleet cannot carry the TRACE wire field: the run must
    succeed, stay bit-identical, keep all master-side spans — and simply
    have no worker-process spans (same degradation shape as HELLO2)."""
    x, y = socket_data
    rec = Recorder()
    runner, w = _socket_run(x, y, wire_version=1, recorder=rec)
    assert not any(s.process.startswith("worker") for s in rec.spans)
    assert rec.find("round") and rec.find("flight")
    w_ref, _ = protocol.train_reference(
        runner.cfg, jax.random.PRNGKey(7), x, y, iters=5,
        survivor_fn=runner.survivor_fn())
    assert (np.asarray(w) == np.asarray(w_ref)).all()
