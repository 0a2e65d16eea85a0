"""Reduction of a JAX profiler trace to device time, idle share, kernel
time and a breakdown.

The profiler writes an `.xplane.pb`; `load` turns it into plain tuples so
that everything after it is arithmetic a test can check on hand-built
events:

* device ops: per device, `(name, start_ns, end_ns)` from the "XLA Ops"
  line of each `/device:TPU:<n>` plane, named by the HLO instruction
  (`op_name`). A loop's op (`while`) spans the ops of its body, which are
  events of their own on the same line;
* host events: `(name, start_ns, end_ns)` from every line of the host
  planes (Python annotations, dispatch, transfers).

The measured window is the host annotation `WINDOW` that the harness opens
around the timed calls, so device and host times share the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def load(trace_dir: str) -> tuple:
    """(device_ops {device id: [(name, t0, t1)]}, host_events [(name, t0,
    t1)]) from the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device_ops, host, seen = {}, [], []
    for plane in data.planes:
        seen.append(f"{plane.name}: {[line.name for line in plane.lines]}")
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(e.name), int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            device_ops[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events]
    if not any(device_ops.values()):
        raise ValueError(f"no {OPS_LINE!r} events on a TPU plane; the trace "
                         f"holds {seen}")
    return device_ops, host


def op_name(text: str) -> str:
    """The HLO instruction name of a TPU "XLA Ops" event, whose name is the
    instruction's whole text: `%spamm_mm_worklist.1 = f32[...] custom-call(
    ...)` -> `spamm_mm_worklist.1`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def window_of(host_events) -> tuple:
    """(t0, t1) of the harness's window annotation."""
    spans = [(t0, t1) for name, t0, t1 in host_events if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s[0] for s in spans), max(s[1] for s in spans)


def clip(events, window) -> list:
    """Events cut to the window; those wholly outside are dropped."""
    w0, w1 = window
    out = []
    for name, t0, t1 in events:
        a, b = max(t0, w0), min(t1, w1)
        if b > a:
            out.append((name, a, b))
    return out


def union(events) -> list:
    """Merged [t0, t1) intervals covered by any event, in time order."""
    merged = []
    for _, t0, t1 in sorted(events, key=lambda e: e[1]):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return merged


def busy_ns(events, window) -> int:
    """Length of the union of the device's op intervals inside the window."""
    return sum(b - a for a, b in union(clip(events, window)))


def idle_share(device_ops: dict, window) -> float:
    """1 - mean over devices of busy time over the window, in [0, 1]."""
    span = window[1] - window[0]
    busy = [busy_ns(ops, window) for ops in device_ops.values()]
    return 1.0 - sum(busy) / len(busy) / span


def kernel_ns(events, window, prefixes) -> int:
    """Summed device time of ops whose name starts with one of `prefixes`,
    inside the window."""
    return sum(b - a for name, a, b in clip(events, window)
               if name.startswith(tuple(prefixes)))


def top_ops(device_ops: dict, window, k: int = 10) -> list:
    """[name, seconds] of the k ops with the most device time in the window,
    averaged over devices. An op's name is its HLO name without the numeric
    suffix XLA adds to each instance (`fusion.12` -> `fusion`)."""
    tot = {}
    for ops in device_ops.values():
        for name, a, b in clip(ops, window):
            key = re.sub(r"[.:]\d+$", "", name)
            tot[key] = tot.get(key, 0) + (b - a)
    n = max(len(device_ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def idle_gaps(device_ops: dict, host_events, window, k: int = 10) -> list:
    """[name, seconds] of the k longest intervals inside the window in which
    device 0 (the lowest id) ran no op, each named by the shortest host event
    that covers the whole gap (what the host was doing), or "unattributed"."""
    dev = device_ops[min(device_ops)]
    busy = union(clip(dev, window))
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for g0, g1 in gaps:
        cover = [(t1 - t0, name) for name, t0, t1 in host_events
                 if t0 <= g0 and t1 >= g1 and name != WINDOW]
        name = min(cover)[1] if cover else "unattributed"
        out.append([name, (g1 - g0) / 1e9])
    return out
