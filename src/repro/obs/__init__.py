"""Runtime observability for the repro solver stack.

Three layers, all host-side (nothing here is ever traced into a jitted
program, so enabling observability cannot change compiled executables or
numerics):

  * :mod:`repro.obs.trace` — span/event tracer with an in-memory ring
    buffer and a JSONL exporter; every span is also a profiler
    annotation ``concord.<name>``, on the timeline of the device ops.
  * :mod:`repro.obs.metrics` — counters, gauges and exponential-bucket
    latency histograms (p50/p95/p99) with Prometheus-text and JSON
    snapshot exporters.
  * :mod:`repro.obs.commwatch` — static-vs-measured communication
    reconciliation: the collective schedule of a distributed solve is
    extracted from its jaxpr at dispatch time, expanded with the solve's
    own observed trip counts, and checked for EXACT per-(prim, axes)
    count and bytes-on-wire equality against the analytic
    ``core.costmodel.comm_volume`` predictions (the CA303 contract).

The estimator scopes the tracer to ``SolverConfig.obs = "off" |
"summary" | "trace"`` for each call; at ``"off"`` (the default) the ring
records nothing and the spans are profiler annotations only.
"""
from __future__ import annotations

from .metrics import MetricsRegistry, get_registry
from .trace import Span, Tracer, get_tracer

__all__ = [
    "MetricsRegistry",
    "Span",
    "Tracer",
    "get_registry",
    "get_tracer",
]
