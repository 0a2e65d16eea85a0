"""Host-side span tracer with Chrome-trace/Perfetto JSON export.

Spans are wall-clock (`time.perf_counter_ns`) intervals around HOST-side
phases of a run: plan freeze, plan-store I/O, compiled prefill, each decode
step, reshard probe / re-cut, cache permute. They deliberately measure the
dispatch+block window (the engine blocks on the step output anyway for its
lockstep loop), not device kernel time.

Every span is also a profiler host event: it opens a
`jax.profiler.TraceAnnotation` of the same name at its start and closes it
at its end, so while a profiler trace is collecting the program's spans sit
on the device trace's clock (outside one, the annotation records nothing).
Library code that has no `SpanTracer` (`core/plan.py`, `core/spamm.py`)
opens profiler-only spans through `annotate`.

Export is the Chrome trace-event JSON format ("traceEvents", `ph: "X"`
complete events, microsecond timestamps), loadable in Perfetto / chrome
about://tracing.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional


def annotate(name: str):
    """A profiler-only span: a `jax.profiler.TraceAnnotation` (a context
    manager) named `name`, recorded while a profiler trace is collecting.
    While JAX traces (under jit, vmap or grad) it is a no-op context: the
    span would time the trace, not the work."""
    import jax  # deferred: the registry side of repro.obs needs no jax

    if not jax.core.trace_ctx.is_top_level():
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class OpenSpan:
    """A span opened now and closed by a later `end()`: for intervals whose
    natural end is a blocking point outside one `with` block (the engine's
    decode loop blocks on step t's output at the top of iteration t+1)."""

    __slots__ = ("_tracer", "name", "args", "t0_ns", "_ann")

    def __init__(self, tracer: Optional["SpanTracer"], name: str, args: dict):
        self._tracer, self.name, self.args = tracer, name, args
        self._ann = None
        if tracer is not None:
            self._ann = annotate(name)
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()

    def end(self, **args) -> int:
        """Close the span (once); returns its end, `perf_counter_ns`."""
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            self._tracer._complete(self.name, self.t0_ns, t1,
                                   {**self.args, **args})
        return t1


class SpanTracer:
    """Append-only list of completed spans. Thread-safe; nested spans are
    reconstructed by the viewer from begin/duration overlap on the same
    (pid, tid) track, so `span()` needs no explicit parent bookkeeping."""

    def __init__(self, enabled: bool = True, process_name: str = "repro",
                 max_events: int = 200_000):
        self.enabled = enabled
        self.process_name = process_name
        self.max_events = max_events
        self.events: list = []
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    def _emit(self, ev: dict):
        with self._lock:
            if len(self.events) < self.max_events:
                self.events.append(ev)

    def _complete(self, name: str, t0_ns: int, t1_ns: int, args: dict):
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,   # µs
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        self._emit(ev)

    @contextmanager
    def span(self, name: str, **args):
        """Time a host-side phase; extra kwargs become viewer-visible args."""
        if not self.enabled:
            yield
            return
        s = OpenSpan(self, name, args)
        try:
            yield
        finally:
            s.end()

    def begin(self, name: str, **args) -> OpenSpan:
        """Open a span that a later `OpenSpan.end()` closes. A disabled
        tracer returns a span that records nothing (its `end()` still
        reads the clock)."""
        return OpenSpan(self if self.enabled else None, name, args)

    def instant(self, name: str, **args):
        """Zero-duration marker (e.g. 'reshard committed')."""
        if not self.enabled:
            return
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        self._emit(ev)

    def span_names(self) -> set:
        with self._lock:
            return {e["name"] for e in self.events}

    def chrome_trace(self) -> dict:
        """The trace document; `export(path)` writes it."""
        with self._lock:
            events = list(self.events)
        meta = [{
            "name": "process_name", "ph": "M", "pid": os.getpid(), "tid": 0,
            "args": {"name": self.process_name},
        }]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


@contextmanager
def _null_span():
    yield


def maybe_span(tracer: Optional[SpanTracer], name: str, **args):
    """Span when a tracer is attached and enabled, no-op otherwise — lets
    instrumented code read as one line without None-checks at call sites."""
    if tracer is None or not tracer.enabled:
        return _null_span()
    return tracer.span(name, **args)
