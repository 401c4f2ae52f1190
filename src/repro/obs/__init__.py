"""``repro.obs`` — process-wide observability: one tracing system plus
metrics.

Importing it never imports jax (stdlib only at import):

* :mod:`repro.obs.tracing` — one :class:`Tracer` with two outputs.
  *For operators*: a bounded Chrome trace-event ring (Perfetto /
  ``chrome://tracing``; one process row per replica, an engine lane and
  one lane per request, each request's ``queue``, ``prefill`` and
  ``decode`` spans once per request) with wall-clock *and* engine-tick
  timestamps. *For the device trace*: :meth:`Tracer.phase` wraps each
  leaf of the engine's tick and the trainer's step (``serve.admit``,
  ``serve.chunk``, ``serve.first_token``, ``serve.decode``,
  ``serve.readback``, ``serve.emit``, ``serve.compile``; ``train.batch``,
  ``train.dispatch``, ``train.readback``) in a
  ``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` session records
  them on the device's clock under their plain names, tracing on or off.
  :data:`NULL_TRACER`, the default, only annotates. Device programs carry
  stable names of their own (``jit_serve_decode``, ``jit_train_step``,
  the Pallas kernels' ``name=``); kernel call sites are not annotated,
  since they run under ``jit`` and an annotation there fires only while
  tracing.
* :mod:`repro.obs.registry` — typed :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` primitives plus callback collectors in one
  lock-protected :class:`MetricsRegistry`, with a stable JSON snapshot
  schema (:data:`SNAPSHOT_SCHEMA`) and a Prometheus-style text
  exposition. The serving engine, router, cache pool, fault injector and
  compile cache all register into one registry — ONE machine-readable
  telemetry surface instead of five ad-hoc dicts.

:mod:`repro.obs.validate` structurally validates Chrome trace-event JSON
(every event carries ``ph/ts/pid/tid/name``, complete spans properly
nested per track) — the CI artifact gate and the tests share it:
``python -m repro.obs.validate trace.json``.
"""

from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                SNAPSHOT_SCHEMA)
from repro.obs.tracing import NULL_TRACER, NullTracer, Tracer

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "SNAPSHOT_SCHEMA",
]
