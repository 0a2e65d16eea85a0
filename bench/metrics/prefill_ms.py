"""Median of the engine's `prefill` spans in the window: from dispatch of
the prefill step to the first token on the host (program span)."""
import numpy as np


def read(run):
    d = [e["dur"] / 1e3 for e in run.spans if e["name"] == "prefill"]
    return float(np.median(d)) if d else None
