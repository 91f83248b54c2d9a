"""The program's phases on the profiler's clock (DESIGN.md §11).

``obs.trace.phase`` opens a ``cpml.<name>`` profiler annotation around a
host phase (and the live recorder's span); ``engine`` names the device ops
of a round with ``jax.named_scope``.  Checked here on CPU profiles and on
the compiled HLO text.
"""
from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterRunner, make_latency
from repro.core import protocol
from repro.core.protocol import decode, engine
from repro.data import synthetic
from repro.obs.trace import NULL_RECORDER, Recorder, phase


def _profiled(tmp_path, fn) -> list[tuple[str, float, float]]:
    """fn() under the profiler; the host events (name, start, end)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def test_phase_opens_profiler_annotation_and_recorder_span(tmp_path):
    rec = Recorder()

    def body():
        with phase("probe_live", rec, round=3) as span:
            assert span is rec.spans[-1]
        with phase("probe_null") as span:
            assert span is None
        with phase("probe_null_explicit", NULL_RECORDER):
            pass

    events = {name for name, _, _ in _profiled(tmp_path, body)}
    assert {"cpml.probe_live", "cpml.probe_null",
            "cpml.probe_null_explicit"} <= events
    # the recorder's args never reach the annotation's name
    assert not any(n.startswith("cpml.probe_live#") for n in events)
    (span,) = rec.spans
    assert span.name == "probe_live" and span.args == {"round": 3}
    assert not span.open
    assert NULL_RECORDER.spans == ()


def test_phase_closes_its_span_when_the_body_raises():
    rec = Recorder()
    with pytest.raises(ValueError):
        with phase("boom", rec):
            raise ValueError("x")
    assert not rec.open_spans() and [s.name for s in rec.spans] == ["boom"]


def _round_args(cfg):
    x, y = synthetic.mnist_like(jax.random.PRNGKey(0), m=64, d=8)
    state = engine.setup(cfg, jax.random.PRNGKey(1), x, y)
    dmat, order = engine.survivor_round(cfg, None)
    xty2 = engine._w_internal(cfg, state.xty)
    w2 = engine._w_internal(cfg, state.w)
    return state, x, y, jnp.asarray(dmat), jnp.asarray(order), xty2, w2


def _compiled_text(program: str) -> str:
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    state, x, y, dmat, order, xty2, w2 = _round_args(cfg)
    scale = (jnp.float32(0.1), jnp.int32(state.m))
    if program == "round":
        lowered = engine._round_jit.lower(
            cfg, jax.random.PRNGKey(2), w2, state.x_shares, state.xq_parts,
            state.y_parts, xty2, dmat, order, None, *scale)
    else:
        iters = 3
        sched = engine.make_schedule(cfg, jax.random.PRNGKey(2), iters,
                                     state.mk)
        lowered = engine._train_scan.lower(
            cfg, 0, w2, state.x_shares, state.xq_parts, state.y_parts, xty2,
            sched.keys, sched.decode_mats, sched.orders, None, *scale,
            state.xq_real[: state.m], state.y[: state.m])
    return lowered.compile().as_text()


@pytest.mark.parametrize("program", ["round", "train_scan"])
def test_compiled_round_names_its_device_scopes(program):
    text = _compiled_text(program)
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in (engine.SCOPE_ENCODE, engine.SCOPE_WORKER,
                  engine.SCOPE_DECODE):
        assert any(scope in n.split("/") for n in op_names), scope


def test_compiled_dataset_encode_names_its_scope():
    """Every op of the compiled dataset encode lies under its device scope."""
    from repro.core.protocol import encode
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    x, _ = synthetic.mnist_like(jax.random.PRNGKey(0), m=64, d=8)
    text = encode._encode_dataset.lower(
        cfg, jax.random.PRNGKey(1), x).compile().as_text()
    ops = [n for n in re.findall(r'op_name="([^"]*)"', text)
           if n.startswith("jit(")]            # the parameters carry names
    assert ops
    assert all(engine.SCOPE_ENCODE_DATASET in n.split("/") for n in ops)


def test_a_cached_build_without_the_scopes_does_not_hide_them(tmp_path):
    """JAX's compile cache keys on op metadata here, so an executable
    cached from the same program without the scopes is not reused."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def program(scoped):
        def f(x):
            if scoped:
                with jax.named_scope(engine.SCOPE_WORKER):
                    return jnp.sin(x) * 3
            return jnp.sin(x) * 3
        return jax.jit(f)

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        x = jnp.arange(8.0)
        program(False)(x).block_until_ready()
        assert os.listdir(tmp_path)          # the scope-less build is cached
        text = program(True).lower(x).compile().as_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert engine.SCOPE_WORKER in text


def test_scopes_leave_the_round_bit_identical():
    """named_scope is metadata: the scan still equals the per-step loop."""
    cfg = protocol.CPMLConfig(N=8, K=2, T=1, r=1)
    x, y = synthetic.mnist_like(jax.random.PRNGKey(0), m=64, d=8)
    key = jax.random.PRNGKey(5)
    w_scan, _ = engine.train(cfg, key, x, y, 3)
    w_loop, _ = engine.train_reference(cfg, key, x, y, 3)
    assert (np.asarray(w_scan) == np.asarray(w_loop)).all()


def test_decode_solve_opens_only_on_a_new_arrival_order(tmp_path):
    """A round whose responder order is new solves its decode matrix once
    (one ``cpml.decode_solve``); the same order again hits the cache."""
    cfg = protocol.CPMLConfig(N=6, K=1, T=1, r=1)
    x, y = synthetic.mnist_like(jax.random.PRNGKey(42), m=64, d=8)
    runner = ClusterRunner(cfg, jax.random.PRNGKey(7), x, y,
                           make_latency("deterministic"))
    decode._cached_decode_matrix.cache_clear()

    def rounds():
        for t in range(3):
            runner.step_round(t, 3)
            jax.block_until_ready(runner.w2)

    events = _profiled(tmp_path, rounds)
    spans = sorted((s, e) for n, s, e in events if n == "cpml.round")
    solves = [s for n, s, _ in events if n == "cpml.decode_solve"]
    assert len(spans) == 3
    per_round = [sum(lo <= s < hi for s in solves) for lo, hi in spans]
    assert per_round == [1, 0, 0]
    orders = {tuple(runner.traces[t].responders[: cfg.threshold])
              for t in range(3)}
    assert len(orders) == 1          # the deterministic model repeats it
    names = {n for n, _, _ in events}
    assert {"cpml.fence", "cpml.round_key", "cpml.dispatch", "cpml.collect",
            "cpml.decode_matrix", "cpml.round_program"} <= names
