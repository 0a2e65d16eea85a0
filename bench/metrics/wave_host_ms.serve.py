"""Median over the window's waves of the engine's `wave` span self time:
its duration less the parts that its `prefill`, `prefill_chunk` and
`decode_step` spans cover. That is the engine's host time per wave outside
its device steps: the wave's set-up before the prefill dispatch, the token
bookkeeping between steps, and the stats fetch and aggregation after the
last token (program span)."""
import numpy as np

STEPS = ("prefill", "prefill_chunk", "decode_step")


def covered(intervals) -> float:
    """Length of the union of (t0, t1) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def read(run):
    waves = [e for e in run.spans if e["name"] == "wave"]
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in run.spans
             if e["name"] in STEPS]
    own = []
    for w in waves:
        w0, w1 = w["ts"], w["ts"] + w["dur"]
        inside = [(max(a, w0), min(b, w1)) for a, b in steps
                  if b > w0 and a < w1]
        own.append((w1 - w0 - covered(inside)) / 1e3)
    return float(np.median(own)) if own else None
