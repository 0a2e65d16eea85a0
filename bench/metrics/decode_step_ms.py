"""Median of the engine's `decode_step` spans in the window: from dispatch
of one cached decode step to its token on the host (program span)."""
import numpy as np


def read(run):
    d = [e["dur"] / 1e3 for e in run.spans if e["name"] == "decode_step"]
    return float(np.median(d)) if d else None
