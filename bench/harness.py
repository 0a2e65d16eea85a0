"""Runs one benchmark cell once and prints its result line.

Everything that belongs to one cell is found by name from
`BENCHMARK.json`: the configuration file it names, the traffic file
`bench/traffic/<traffic>.json`, the driver `bench/drivers/<driver>.py`
that the configuration names, and one reader `bench/metrics/<metric>.py`
per metric. A later cell or metric is added as files, not as edits here.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and with `--trace 1`
`breakdown`), then `checks`, each number compared beside its limit. The
same comparisons are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(manifest: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's manifest entry, configuration, traffic, and the names of
    the end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if listed(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if ("workloads" in m and workload in m["workloads"])
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def reader(metric: str, root: Path = ROOT):
    """The `read(run)` function of bench/metrics/<metric>.py."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a driver measured in one run. Readers take what they need and
    return None where the run holds nothing for them."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    checks: list = dataclasses.field(default_factory=list)
    # serving: one dict per wave of the window (submit_s, done_s, batch,
    # prompt_len, new_tokens, out_tokens); engine spans inside the window
    waves: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)
    # products: how many the window completed and the work of one
    products: int = 0
    product_work: Optional[dict] = None
    # --trace 1: device ops per used device, host events, window (trace
    # clock, ns)
    trace: Optional[dict] = None
    reference_s: float = 0.0         # the correctness check, after the window


class Window:
    """The measured window: host clock around the timed calls and, when
    tracing, the profiler around them with a `bench_window` annotation so
    the trace knows where the window lies."""

    def __init__(self, trace_dir: Optional[str]):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None

    @contextlib.contextmanager
    def open(self):
        import jax

        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench_window"):
                self.t0 = time.perf_counter()
                yield self
                self.t1 = time.perf_counter()
        finally:
            if self.trace_dir:
                jax.profiler.stop_trace()

    @property
    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), caching every
    program however quick its compile, so that only the first run of a
    cell in a checkout compiles."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def accelerator(chips: int) -> list:
    """The first `chips` TPU devices; NoAccelerator otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's first device is "
                            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of `devices` (0 where the backend
    keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def attach_trace(run: Run, trace_dir: str) -> None:
    """Read the profiler's trace into `run.trace`: the ops of the devices
    the cell used, the host events, and the window on the trace's clock."""
    from bench import devtrace

    device_ops, host = devtrace.load(trace_dir)
    used = {d.id for d in run.devices}
    ops = {i: e for i, e in device_ops.items() if i in used}
    if not ops:
        raise RuntimeError(f"the trace holds no ops of devices {sorted(used)}"
                           f" (planes for {sorted(device_ops)})")
    window = devtrace.window_of(host)
    if not any(devtrace.busy_ns(e, window) for e in ops.values()):
        raise RuntimeError("no device op falls inside the window's host "
                           "annotation: the trace's device and host clocks "
                           "do not line up")
    run.trace = {"device_ops": ops, "host": host, "window": window}


def metrics_of(run: Run, specs: list) -> dict:
    """Every metric of `specs` as its reader gives it. The cell's metrics
    are listed for it because their readers find something to read there,
    so a reader that returns None is a fault of the run (a kernel renamed,
    a span gone) and fails it, naming what the trace did hold."""
    out = {}
    for m in specs:
        value = reader(m["name"])(run)
        if value is None:
            seen = ""
            if run.trace is not None:
                seen = f"; the window's top device ops: {breakdown(run)['device_ops']}"
            raise RuntimeError(f"metric {m['name']} read nothing in this "
                               f"run{seen}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(run: Run) -> dict:
    from bench import devtrace

    t = run.trace
    return {"device_ops": devtrace.top_ops(t["device_ops"], t["window"]),
            "idle_gaps": devtrace.idle_gaps(t["device_ops"], t["host"],
                                            t["window"])}


def merged(base: dict, over: dict) -> dict:
    """`base` with `over`'s keys replaced; dict values merge one level."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**base[k], **v} if isinstance(v, dict) else v
    return out


def measure(spec: dict, seed: int, seconds: float, trace: bool, *,
            t0: float, devices, peaks: dict) -> Run:
    """Drive the cell's driver once: set-up, window, correctness, and with
    `trace` the profiler's trace read into the run."""
    driver = importlib.import_module(f"bench.drivers.{spec['config']['driver']}")
    run = Run(cell=spec["cell"], config=spec["config"],
              traffic=spec["traffic"], seed=seed, seconds=seconds,
              devices=list(devices), peaks=peaks)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        driver.run(run, Window(trace_dir), t0=t0)
        if trace:
            attach_trace(run, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return run


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t0: float, root: Path = ROOT, require_tpu: bool = True,
            devices=None, overrides: Optional[dict] = None) -> dict:
    """Run the cell once and return the result object. `require_tpu`,
    `devices` and `overrides` ({"config": {...}, "traffic": {...}} merged
    into the cell's files) exist for the tests, which drive a run on the
    CPU at a small size."""
    spec = cell_spec(load_manifest(root), workload, root)
    for part, over in (overrides or {}).items():
        spec[part] = merged(spec[part], over)
    peaks = {}
    if require_tpu:
        from bench.peaks import peaks_for

        devices = accelerator(spec["cell"]["chips"])
        enable_compile_cache(root)
        peaks = peaks_for(devices[0].device_kind)
    run = measure(spec, seed, seconds, trace, t0=t0, devices=devices,
                  peaks=peaks)
    correct = bool(run.checks) and all(c.ok for c in run.checks)
    import jax

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        from bench import devtrace

        t = run.trace
        w = t["window"]
        busy = [devtrace.busy_ns(ops, w) for ops in t["device_ops"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (w[1] - w[0]) / 1e9
        result["metrics"] = metrics_of(run, spec["per_layer"])
        result["device"] = device
        result["breakdown"] = breakdown(run)
    else:
        result["metrics"] = metrics_of(run, spec["end_to_end"])
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    print(f"bench: window {run.window_s:.3f} s, correctness check "
          f"{run.reference_s:.3f} s after it", file=sys.stderr)
    if run.waves:
        print("bench: wave seconds " + " ".join(
            f"{w['done_s'] - w['submit_s']:.3f}" for w in run.waves),
            file=sys.stderr)
    return result


def main(argv, *, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), t0=t0)
    except NoAccelerator as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
