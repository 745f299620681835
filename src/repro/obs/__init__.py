"""Live observability layer: streaming run metrics, worker heartbeats,
an engine self-profiler, and the runtime's spans.

Four pieces (see docs/observability.md):

* :class:`repro.obs.metrics.MetricsRegistry` — online counters / gauges
  / windowed statistics attached to ``ClusterSim`` via the same
  pure-observer contract as ``TraceRecorder``: never consumes engine
  RNG, never pushes events, ``obs=None`` costs one ``is not None``
  check per hook site, and an instrumented run is bit-for-bit identical
  to a bare one (gated against the committed engine digests in
  tests/test_obs.py; overhead <5% gated by ``benchmarks.run --only
  obs_bench``).
* :mod:`repro.obs.emit` — periodic simulated-time snapshot emission to
  structured jsonl and Prometheus text-exposition format, plus the
  wall-clock :class:`~repro.obs.emit.Heartbeat` channel the ensemble /
  sweep worker pools stream per-cell progress over.
* :class:`repro.obs.profiler.EngineProfiler` — engine phase timers
  (event-loop breakdown: sched passes, fault handling, allocation,
  record appends) exposed as a self-profiling summary.
* :mod:`repro.obs.spans` — ``span(name, **ids)``, the trainer's,
  checkpoint manager's and server's host spans on the profiler's clock,
  kept in process only while a profiler capture runs, plus
  ``repro.compile`` records from ``jax.monitoring``.

Front door for recorded snapshot streams::

    PYTHONPATH=src python -m repro.obs.report RUN.jsonl
"""
from repro.obs.emit import (Heartbeat, JsonlWriter, read_jsonl,
                            to_prometheus)
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import EngineProfiler

__all__ = ["MetricsRegistry", "EngineProfiler", "Heartbeat",
           "JsonlWriter", "read_jsonl", "to_prometheus"]
