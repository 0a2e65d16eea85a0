"""Observability subsystem: labeled metrics, host spans, cost residuals.

`Observability` bundles the three channels one runtime (engine, train loop,
benchmark) shares:

  * `registry` — `MetricsRegistry` of counters/gauges/histograms with label
    sets (`layer`, `phase`, `site`, `dtype`); the gating stats the compiled
    steps return and the engine's latency measurements feed it. Export with
    `write_metrics(path)` (Prometheus text) or `registry.snapshot()` (JSON,
    rides `benchmarks.report.write_bench_json(metrics=...)`).
  * `tracer` — `SpanTracer` host-side spans (freeze, plan-store I/O,
    wave, prefill, decode steps, reshard probe/re-cut, cache permute), each
    also a profiler host event; export with `write_trace(path)`
    (Chrome-trace/Perfetto JSON).
  * `residual` — `CostResidualTracker` pairing cost-model predictions with
    measured wall-clock per phase.

Pass `obs=False` to an instrumented component for a hard-off bundle: no
spans, no latency reads, no registry feed and no cost channel. The gating
stats ride the compiled steps as outputs either way, so the compiled
programs are the same with the bundle on or off.
"""
from __future__ import annotations

from typing import Optional, Union

from repro.obs.registry import (  # noqa: F401  (re-exported surface)
    Counter, FRACTION_BUCKETS, Gauge, Histogram, IMBALANCE_BUCKETS,
    LATENCY_BUCKETS_S, MetricsRegistry, RESIDUAL_LOG2_BUCKETS,
    parse_prometheus,
)
from repro.obs.residual import CostResidualTracker  # noqa: F401
from repro.obs.tracer import SpanTracer, maybe_span  # noqa: F401


class Observability:
    """One bundle per runtime; share it across components of a run (engine +
    CLI, or train loop + CLI) so the exported dump is the whole story."""

    def __init__(self, enabled: bool = True, process_name: str = "repro"):
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer(enabled=enabled, process_name=process_name)
        self.residual = CostResidualTracker(self.registry)

    def span(self, name: str, **args):
        return maybe_span(self.tracer if self.enabled else None, name, **args)

    def write_metrics(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.registry.render_prometheus())
        return path

    def write_trace(self, path: str) -> str:
        return self.tracer.export(path)

    def summary_table(self) -> str:
        return self.registry.summary_table()

    @classmethod
    def ensure(cls, obs: Union["Observability", bool, None],
               process_name: str = "repro") -> "Observability":
        """Normalize the `obs=` argument instrumented components accept:
        None -> fresh enabled bundle, False -> fresh disabled bundle,
        an existing bundle -> itself."""
        if isinstance(obs, cls):
            return obs
        return cls(enabled=(obs is not False), process_name=process_name)
