"""Routed rows of the held experts over the rows of the expert row tiles
the expert layers ran, over the window's waves, prefill and decode (the
engine's counters, program counter). 100 means no row tile held padding."""


def read(run):
    rows = slots = 0
    for w in run.waves:
        m = w.get("moe")
        if not m or not m.get("tile"):
            return None
        for phase in ("prefill", "decode"):
            if phase in m:
                rows += m[phase]["routed_rows"]
                slots += m[phase]["row_tiles"] * m["tile"]
    return 100.0 * rows / slots if slots else None
