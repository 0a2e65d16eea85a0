"""p95 of the gaps between consecutive `prefill` and `decode_step` span
ends within each wave of the window (a span ends when its token reaches the
host). Each gap counts once per request of its wave: every request of a
wave gets a token at every end, since the traffic sends no EOS and one
token budget per wave. Gaps never cross waves (program span)."""
import numpy as np

STEPS = ("prefill", "decode_step")


def read(run):
    ends = sorted(e["ts"] + e["dur"] for e in run.spans
                  if e["name"] in STEPS)
    gaps = []
    for w in (e for e in run.spans if e["name"] == "wave"):
        w0, w1 = w["ts"], w["ts"] + w["dur"]
        mine = [t for t in ends if w0 <= t <= w1]
        batch = int(w.get("args", {}).get("batch", 1))
        gaps += [(b - a) / 1e3 for a, b in zip(mine, mine[1:])] * batch
    return float(np.percentile(gaps, 95)) if gaps else None
