"""Flight recorder for the cluster runtime (DESIGN.md §11).

Observability substrate: ``trace`` (begin/end spans on a pluggable clock —
SimClock and WallClock runs produce the same trace SHAPE — and ``phase``),
``metrics`` (counters/gauges/histograms with Prometheus-textfile and JSON
exporters), ``export`` (Chrome trace-event / Perfetto JSON, the terminal
waterfall, and the straggler-attribution report).

The recorder is off by default: every instrumented call site holds a
``NullRecorder`` whose methods are no-ops, so it costs nothing unless a run
opts in.

``phase(name, recorder)`` names one phase of the program on the JAX
profiler's host clock, the clock of the device planes in the same trace: a
``jax.profiler.TraceAnnotation("cpml.<name>")`` around the recorder's span
(the NullRecorder's no-op when tracing is off).  Host phases:
``cpml.round``, ``cpml.fence``, ``cpml.round_key``, ``cpml.dispatch``,
``cpml.collect``, ``cpml.decode_matrix``, ``cpml.decode_solve`` (opened
only on a decode-matrix cache miss), ``cpml.round_program`` (the cluster
round), and
``cpml.train``, ``cpml.setup.encode_dataset``, ``cpml.setup.step_size``,
``cpml.setup.schedule`` (a training job), and
``cpml.setup.encode_dataset.block`` around each row block of the sharded
dataset encode.  Device scopes
(``jax.named_scope``, in the compiled ops' ``op_name``):
``cpml_encode_weights``, ``cpml_worker``, ``cpml_decode``,
``cpml_encode_dataset``.  ``REGISTRY`` (``metrics.py``) holds the
process's counters.  The on-chip
benchmark reads them in its traced run (PERF.md §3), where their cost is
measured too: with no profiler session an annotation costs a few hundred
ns.
"""
from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.trace import NULL_RECORDER, NullRecorder, Recorder, Span

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_RECORDER", "NullRecorder", "Recorder", "Span",
]
