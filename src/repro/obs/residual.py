"""Cost-residual channel: predicted vs measured time for the gated GEMMs.

The PR 7 cost model (`core.cost.predict_time_s`) drives autotuning and
re-shard probes, but nothing told us when its calibrated coefficients drift
from reality (new machine, stale `CostProfile`, changed XLA version). This
channel closes the loop: each executed frozen-path GEMM is priced by the
roofline model on the EXECUTED work-list (`cost.predict_plan_static` at
trace time, finished on the host from the valid fraction and bytes the
step returns — `cost.finish_plan_time_s`), the engine pairs the per-phase
prediction sums with the measured host wall-clock of that phase, and the
log2(measured/predicted) ratio lands in a histogram.

Interpretation: a calibrated profile on its own machine should concentrate
mass near 0 (within ±0.5 ≈ 1.4x); a persistent shift means re-run
`benchmarks/autotune.py --calibrate`. Granularity is per phase per wave
(prefill total, decode-step total), NOT per kernel: the host measures whole
steps, so individual GEMMs cannot be paired with sub-step wall-clock. The
per-phase sum is exactly the quantity the autotuner's argmin integrates, so
it is also the right one to validate.
"""
from __future__ import annotations

import math
from typing import Optional

from repro.obs.registry import (MetricsRegistry, RESIDUAL_LOG2_BUCKETS,
                                Histogram)


class CostResidualTracker:
    """Pairs predicted-vs-measured phase times into registry metrics."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.hist: Histogram = registry.histogram(
            "spamm_cost_time_residual_log2",
            help="log2(measured / predicted) wall-clock of gated-GEMM work "
                 "per phase per wave; 0 = calibrated cost model",
            labelnames=("phase",), buckets=RESIDUAL_LOG2_BUCKETS)
        self.predicted_s = registry.counter(
            "spamm_cost_predicted_seconds_total",
            help="cost-model predicted gated-GEMM seconds",
            labelnames=("phase",))
        self.measured_s = registry.counter(
            "spamm_cost_measured_seconds_total",
            help="measured wall-clock seconds of the paired phase",
            labelnames=("phase",))

    def record(self, phase: str, predicted_s: float,
               measured_s: float) -> Optional[float]:
        """Record one pairing; returns the log2 residual (None if either
        side is non-positive — e.g. no gated GEMM executed in the phase)."""
        if predicted_s <= 0.0 or measured_s <= 0.0:
            return None
        r = math.log2(measured_s / predicted_s)
        self.hist.observe(r, phase=phase)
        self.predicted_s.inc(predicted_s, phase=phase)
        self.measured_s.inc(measured_s, phase=phase)
        return r
