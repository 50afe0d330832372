"""Serving observability: span tracing, bounded metrics, flocking
telemetry.

Independent pieces the serving stack emits into (DESIGN.md
section 12):

* ``obs.trace`` / ``obs.export`` — structured span recorder and its
  Chrome/Perfetto ``trace.json`` exporter.  Request lifecycle events
  ride async spans keyed by rid; per-tick phase breakdown rides
  synchronous complete spans, each of which can also be a
  ``jax.profiler`` annotation on the device trace's clock.
* ``obs.registry`` — counter/gauge/histogram registry with fixed-bucket
  streaming histograms (bounded memory), Prometheus text exposition and
  a JSON snapshot.
* ``obs.flocking`` — per-request, per-layer gauges of GRIFFIN
  expert-selection stability (Jaccard overlap + angular distance),
  sampled by a non-donating probe step.
* ``obs.stragglers`` — per-tick step-time histogram and a counter of
  ticks far above the rolling median.

Everything is off by default and compiles to no-ops when disabled: the
null tracer allocates nothing per call, and the registry replaces the
per-step lists ``ServingMetrics`` used to grow without bound.
"""
from repro.obs.registry import Registry, exp_buckets, linear_buckets
from repro.obs.trace import NULL_TRACER, Tracer

__all__ = [
    "Registry",
    "Tracer",
    "NULL_TRACER",
    "linear_buckets",
    "exp_buckets",
]
