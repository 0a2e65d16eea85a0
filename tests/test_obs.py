"""Telemetry subsystem: registry, spans, gating stats and cost residual.

Fast units pin the host-side sinks (registry semantics + Prometheus
round-trip, span tracer + Chrome-trace export, spans as profiler host
events, cost-residual tracker) and
the zero-graph-cost identity `predict_plan_static` + `finish_plan_time_s`
== `predict_plan_time_s` that lets the cost channel arm without changing
the traced graphs. Integration tests drive real engine waves / train steps
and assert the labeled-tap contract: per-(layer, site) cells exist under
`lax.scan`-stacked layers (dense and hybrid stacks) and under `grad`
(custom_vjp fwd path), their sums reproduce the existing per-wave
aggregates EXACTLY, the instrumentation changes neither tokens, trace
counts nor the returned stats (`obs=False` A/B), and no compiled serving
step holds a host callback. The 4-fake-device sharded contract runs in
a subprocess (device count locks at first jax init), mirroring
tests/test_sharded_engine.py.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.configs import ParallelConfig, SpammConfig, get_config
from repro.core import cost
from repro.core import plan as pl
from repro.core import schedule as S
from repro.core.spamm import exponential_decay
from repro.launch.mesh import make_ctx, make_host_mesh
from repro.models import model as M
from repro.obs import (CostResidualTracker, Histogram, MetricsRegistry,
                       Observability, SpanTracer, maybe_span,
                       parse_prometheus)
from repro.serving.engine import Engine, Request

PCFG = ParallelConfig(
    compute_dtype="float32", param_dtype="float32", remat="none",
    attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=32, decode_seq_shard=False,
)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_labels_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("spamm_widgets_total", "w", labelnames=("phase",))
    c.inc(phase="prefill")
    c.inc(2.5, phase="prefill")
    c.inc(phase="decode")
    assert c.value(phase="prefill") == 3.5
    assert c.value(phase="decode") == 1.0
    assert c.value(phase="never") == 0.0          # untouched series reads 0
    with pytest.raises(ValueError):
        c.inc(-1.0, phase="prefill")              # counters only go up
    with pytest.raises(ValueError):
        c.inc()                                    # missing label
    with pytest.raises(ValueError):
        c.inc(phase="prefill", layer=0)            # undeclared label


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    g = reg.gauge("live_imbalance")
    assert g.value() is None
    g.set(1.5)
    g.set(1.2)
    assert g.value() == 1.2


def test_histogram_buckets_quantile_and_recent():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0), keep_recent=3)
    for v in (1.0, 3.0):                           # le=1 and le=4 buckets
        h.observe(v)
    assert h.count() == 2 and h.sum() == 4.0
    # rank interpolation: p50 lands at the first bucket's upper bound,
    # p100 at the second occupied bucket's
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert h.quantile(1.0) == pytest.approx(4.0)
    h.observe(100.0)                               # +Inf bucket...
    assert h.quantile(1.0) == 4.0                  # ...clamps to top finite
    for v in (5.0, 6.0, 7.0, 8.0):
        h.observe(v)
    assert h.recent() == [6.0, 7.0, 8.0]           # bounded raw-sample tail
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))       # must ascend


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help", labelnames=("a",))
    c2 = reg.counter("x_total", "other", labelnames=("a",))
    assert c1 is c2                                # cached by name
    with pytest.raises(ValueError):
        reg.gauge("x_total")                       # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("b",))  # label-set conflict
    with pytest.raises(ValueError):
        reg.counter("0bad name")                   # invalid metric name


def test_prometheus_render_parse_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("serve_waves_total", "waves", labelnames=("phase",))
    c.inc(3, phase="prefill")
    h = reg.histogram("serve_ttft_seconds", "ttft", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render_prometheus()
    back = parse_prometheus(text)
    assert back["serve_waves_total"]["type"] == "counter"
    assert back["serve_waves_total"]["samples"][
        'serve_waves_total{phase="prefill"}'] == 3
    hs = back["serve_ttft_seconds"]["samples"]
    assert hs['serve_ttft_seconds_bucket{le="0.1"}'] == 1
    assert hs['serve_ttft_seconds_bucket{le="1"}'] == 2   # cumulative
    assert hs['serve_ttft_seconds_bucket{le="+Inf"}'] == 2
    assert hs["serve_ttft_seconds_count"] == 2
    assert hs["serve_ttft_seconds_sum"] == pytest.approx(0.55)
    # snapshot is JSON-able (rides write_bench_json(metrics=...))
    json.dumps(reg.snapshot())
    # the end-of-run table mentions every metric with samples
    table = reg.summary_table()
    assert "serve_waves_total" in table and "serve_ttft_seconds" in table


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_and_chrome_export(tmp_path):
    tr = SpanTracer(process_name="repro-test")
    with tr.span("freeze", n=3):
        pass
    step = tr.begin("decode_step", step=0)
    t1 = step.end(tokens=8)
    step.end()                                     # a second end records nothing
    tr.instant("reshard_committed")
    assert tr.span_names() == {"freeze", "decode_step", "reshard_committed"}
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "repro-test"
    dec = [e for e in evs if e["name"] == "decode_step"]
    assert len(dec) == 1 and dec[0]["ph"] == "X"
    assert dec[0]["dur"] == pytest.approx((t1 - step.t0_ns) / 1e3)  # µs
    assert dec[0]["args"] == {"step": 0, "tokens": 8}
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f) == doc                 # valid Perfetto JSON

    off = SpanTracer(enabled=False)
    with off.span("x"):
        pass
    off.begin("y").end()
    assert off.events == []                        # hard-off records nothing
    with maybe_span(None, "z"):                    # None-tracer helper
        pass


def test_tracer_bounds_event_count():
    tr = SpanTracer(max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    assert len(tr.events) == 2                     # never grows unbounded


# ---------------------------------------------------------------------------
# cost-residual tracker
# ---------------------------------------------------------------------------


def test_cost_residual_tracker_records_log2_ratio():
    reg = MetricsRegistry()
    tk = CostResidualTracker(reg)
    r = tk.record("prefill", predicted_s=0.5, measured_s=1.0)
    assert r == pytest.approx(1.0)                 # measured 2x slower
    assert tk.hist.count(phase="prefill") == 1
    assert tk.predicted_s.value(phase="prefill") == 0.5
    assert tk.measured_s.value(phase="prefill") == 1.0
    # non-positive sides (no gated GEMM ran) record nothing
    assert tk.record("decode", 0.0, 1.0) is None
    assert tk.record("decode", 1.0, 0.0) is None
    assert tk.hist.count(phase="decode") == 0


# ---------------------------------------------------------------------------
# cost channel: static-split prediction == the in-trace twin
# ---------------------------------------------------------------------------


def test_predict_plan_static_finish_matches_in_trace_prediction():
    """The telemetry taps price a GEMM as predict_plan_static (host, trace
    time) + finish_plan_time_s (host, from the step's returned stats). The
    split must equal
    predict_plan_time_s on the same plan EXACTLY — this identity is what
    lets armed and unarmed contexts trace identical graphs."""
    a = jnp.asarray(exponential_decay(128, lam=0.8, seed=0))
    b = jnp.asarray(exponential_decay(128, lam=0.8, seed=1))
    coeffs = cost.DEFAULT_COEFFS["interpret"]
    for block_n, levels in ((1, 0), (2, 1)):
        p = pl.plan(a, b, 0.05, tile=32, block_n=block_n, levels=levels,
                    backend="interpret")
        static = cost.predict_plan_static(p, coeffs)
        assert static is not None
        got = cost.finish_plan_time_s(static, float(p.valid_fraction),
                                      float(p.bytes_moved()), coeffs)
        want = float(cost.predict_plan_time_s(p, coeffs))
        # same formula, but the in-trace twin evaluates in f32 (its
        # operands are traced arrays) — agree to f32 precision
        assert got == pytest.approx(want, rel=1e-6)

    class _NoWork:                                 # dense-bitmap shape
        work = None

    assert cost.predict_plan_static(_NoWork(), coeffs) is None


# ---------------------------------------------------------------------------
# reshard controller -> registry publishing
# ---------------------------------------------------------------------------


def test_reshard_publish_incremental_and_idempotent():
    ctl = S.ReshardController(S.ReshardConfig(num_devices=2, every=1))
    v = jnp.asarray(np.ones((8, 8), np.float32))
    ctl.probe(v, 0)
    ctl.probe(v, 1)
    reg = MetricsRegistry()
    ctl.publish(reg)
    probes = reg.counter("spamm_reshard_probes_total")
    events = reg.counter("spamm_reshard_events_total")
    imb = reg.histogram("spamm_partition_imbalance")
    assert probes.value() == 2
    assert events.value() == 0                     # uniform v never re-cuts
    assert imb.count() == 2
    assert reg.gauge("spamm_partition_imbalance_live").value() is not None
    ctl.publish(reg)                               # cursor: no double count
    assert probes.value() == 2
    ctl.probe(v, 2)
    ctl.publish(reg)                               # only the delta lands
    assert probes.value() == 3 and imb.count() == 3


# ---------------------------------------------------------------------------
# engine integration: labeled taps under the scanned stack
# ---------------------------------------------------------------------------


def _mk_reqs(rng, cfg, b, plen, max_new):
    return [Request(prompt=rng.integers(1, cfg.vocab, size=plen)
                    .astype(np.int32), max_new_tokens=max_new)
            for _ in range(b)]


def _run_wave(arch="musicgen-large", obs=None, max_new=4, plen=16, b=2,
              tile=4, seed=0):
    cfg = get_config(arch).reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.05, tile=tile, backend="jnp")
    eng = Engine(cfg, PCFG, ctx, params, max_len=plen + max_new + 8,
                 spamm_cfg=sc, obs=obs)
    reqs = _mk_reqs(np.random.default_rng(seed), cfg, b, plen, max_new)
    outs = eng.generate(reqs)
    return cfg, eng, reqs, outs


def _assert_cells_sum_to_aggregates(sp):
    """Per-(layer, site) cells must reproduce the wave aggregates exactly:
    the breakdown re-bins the SAME taps, so counts/bytes sum and nothing
    leaks (a cell landing outside the aggregate, or an unlabeled tap
    silently entering a cell, both break the equality)."""
    cells = [c for sites in sp["per_layer"].values() for c in sites.values()]
    assert sum(c["gated_gemms"] for c in cells) == sp["gated_gemms"]
    assert sum(c["decode_gated_gemms"] for c in cells) == \
        sp["decode_gated_gemms"]
    total_bytes = sum(c["gemm_bytes_moved"] or 0.0 for c in cells)
    want_bytes = (sp["gemm_bytes_moved"] or 0.0) + \
        (sp["decode_gemm_bytes_moved"] or 0.0)
    assert total_bytes == pytest.approx(want_bytes, rel=1e-9)
    for c in cells:
        for k in ("valid_fraction", "decode_valid_fraction"):
            if c[k] is not None:
                assert 0.0 <= c[k] <= 1.0


def test_engine_per_layer_attribution_under_scan():
    cfg, eng, reqs, _ = _run_wave(max_new=4)
    sp = reqs[0].out["spamm"]
    # every scanned layer shows up, labeled 0..L-1, with named GEMM sites
    assert set(sp["per_layer"]) == set(range(cfg.num_layers))
    for sites in sp["per_layer"].values():
        assert set(sites) <= {"wq", "wk", "wv", "wo", "w1", "w2", "w3"}
        assert sites                                # never an empty layer
    _assert_cells_sum_to_aggregates(sp)
    # latency channel: TTFT plus per-decode-step stats from the wave
    lat = sp["latency"]
    assert lat["ttft_s"] > 0.0
    assert lat["decode_steps"] == 3                 # max_new-1 measured gaps
    assert lat["decode_mean_s"] > 0.0
    assert lat["decode_p50_s"] <= lat["decode_p95_s"]
    # cost channel: per-phase predicted/measured pairing with log2 residual
    cres = sp["cost_residual"]
    assert set(cres) <= {"prefill", "decode"} and cres
    for ph in cres.values():
        assert ph["predicted_s"] > 0.0 and ph["measured_s"] > 0.0
        assert math.isfinite(ph["log2_ratio"])
    # instrumentation never re-traces the step functions
    assert eng.trace_counts == {"prefill": 1, "decode": 1}


def test_engine_registry_and_spans_cross_check():
    cfg, eng, reqs, _ = _run_wave(max_new=4)
    sp = reqs[0].out["spamm"]
    reg = eng.obs.registry
    # the registry's labeled counter re-aggregates to the wave totals
    gemms = reg.counter("spamm_gated_gemms_total", "",
                        labelnames=("phase", "layer", "site"))
    assert sum(gemms.series().values()) == \
        sp["gated_gemms"] + sp["decode_gated_gemms"]
    assert reg.histogram("serve_ttft_seconds").count() == 1
    assert reg.histogram("serve_decode_step_seconds").count() == \
        sp["latency"]["decode_steps"]
    assert reg.counter("serve_waves_total").value() == 1
    # spans cover the wave's host phases and export as valid Chrome JSON
    names = eng.obs.tracer.span_names()
    assert {"freeze", "prefill", "decode_step", "wave", "wave_open",
            "wave_close"} <= names
    # wave_open ends before the prefill opens; wave_close starts after the
    # last decode step ends; both lie inside the wave
    ev = {e["name"]: e for e in eng.obs.tracer.events}
    end = {k: e["ts"] + e["dur"] for k, e in ev.items()}
    assert ev["wave"]["ts"] <= ev["wave_open"]["ts"]
    assert end["wave_open"] <= ev["prefill"]["ts"]
    assert end["decode_step"] <= ev["wave_close"]["ts"]
    assert end["wave_close"] <= end["wave"]
    json.dumps(eng.obs.tracer.chrome_trace())
    # Prometheus dump of a real run round-trips through the CI parser
    back = parse_prometheus(reg.render_prometheus())
    assert "spamm_valid_fraction" in back
    assert "spamm_gemm_bytes_total" in back


def test_engine_obs_false_is_bit_identical_and_silent():
    """obs=False is the A/B baseline: same tokens, same trace counts, no
    spans, no latency/cost channels. The gating stats ride the compiled
    steps either way, so everything the steps return — counts per phase,
    fractions, bytes, the per-layer cells — is the same with obs on and
    off; only the host-clock channels (latency, and the cost residual
    whose measured side is that clock) need obs."""
    cfg, eng_i, reqs_i, outs_i = _run_wave(max_new=4, seed=3)
    _, eng_b, reqs_b, outs_b = _run_wave(max_new=4, seed=3, obs=False)
    for a, b in zip(outs_i, outs_b):
        np.testing.assert_array_equal(a, b)
    assert eng_b.trace_counts == eng_i.trace_counts == \
        {"prefill": 1, "decode": 1}
    sp_i, sp_b = reqs_i[0].out["spamm"], reqs_b[0].out["spamm"]
    assert "latency" not in sp_b and "cost_residual" not in sp_b
    assert eng_b.obs.tracer.events == []
    assert eng_b.obs.registry.metrics() != eng_i.obs.registry.metrics()
    host_clock = {"latency", "cost_residual"}
    assert {k: v for k, v in sp_i.items() if k not in host_clock} == sp_b
    # every frozen GEMM site of every layer reports once per execution:
    # one prefill, max_new - 1 decode steps
    sites = len(sp_b["per_layer"][0])
    assert sites == 6                              # wq wk wv wo w1 w2
    assert sp_b["gated_gemms"] == cfg.num_layers * sites
    assert sp_b["decode_gated_gemms"] == cfg.num_layers * sites * 3
    _assert_cells_sum_to_aggregates(sp_b)


def test_step_stats_aggregation_matches_a_per_tap_loop():
    """The engine bins a wave's returned step stats in numpy; a plain loop
    over every (execution, row) value is the reference: same aggregates,
    same per-layer cells, same registry series. The steps mix two shapes
    of one phase (two layouts), a cell that several rows feed (MoE experts
    share a site), shard-stacked executions and taps without a byte count
    (NaN)."""
    from repro.core.module import StepStats

    cfg = get_config("musicgen-large").reduced()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    eng = Engine(cfg, PCFG, make_ctx(make_host_mesh()), params, max_len=32,
                 spamm_cfg=SpammConfig(enable=True, tau=0.05, tile=4,
                                       backend="jnp"))
    rng = np.random.default_rng(0)
    static = (1e-5, 64.0, 4, 1)
    lay_a = ((0, "wq", static), (0, "expert_w1", None),
             (0, "expert_w1", None), (1, "wq", static))
    lay_b = ((0, "wq", (2e-5, 32.0, 4, 1)), (1, "wq", static))

    def values(shape, nan_rows=()):
        v = rng.random(shape + (2,)).astype(np.float32)
        v[..., 1] *= 1e4
        for r in nan_rows:
            v[..., r, 1] = np.nan
        return v

    steps = [("prefill", StepStats(values((len(lay_a),), (1, 2)), lay_a)),
             ("prefill", StepStats(values((len(lay_b),)), lay_b)),
             ("decode", StepStats(values((3, len(lay_a)), (1,)), lay_a)),
             ("decode", StepStats(values((3, len(lay_a)), (1,)), lay_a))]
    st = eng._spamm_stats(steps, *eng._counters(), ttft_s=1.0,
                          decode_lat=[0.1, 0.2])

    ref: dict = {}          # (phase, layer, site) -> [fractions], [bytes]
    for phase, s in steps:
        v = np.asarray(s.values, np.float64).reshape(-1, len(s.layout), 2)
        for row in v:
            for (layer, site, _), (f, nb) in zip(s.layout, row):
                c = ref.setdefault((phase, layer, site), ([], []))
                c[0].append(f)
                c[1].append(nb)
    for decode, (vf, n, nb) in ((False, ("valid_fraction", "gated_gemms",
                                         "gemm_bytes_moved")),
                                (True, ("decode_valid_fraction",
                                        "decode_gated_gemms",
                                        "decode_gemm_bytes_moved"))):
        fs = [f for k, c in ref.items() if (k[0] == "decode") == decode
              for f in c[0]]
        bs = [b for k, c in ref.items() if (k[0] == "decode") == decode
              for b in c[1] if np.isfinite(b)]
        assert st[n] == len(fs)
        assert st[vf] == pytest.approx(np.mean(fs), rel=1e-12)
        assert st[nb] == pytest.approx(sum(bs), rel=1e-12)
    for layer, sites in st["per_layer"].items():
        for site, cell in sites.items():
            for phase, vf, n in (("prefill", "valid_fraction", "gated_gemms"),
                                 ("decode", "decode_valid_fraction",
                                  "decode_gated_gemms")):
                fs = ref.get((phase, layer, site), ([], []))[0]
                assert cell[n] == len(fs)
                if fs:
                    assert cell[vf] == pytest.approx(np.mean(fs), rel=1e-12)
    pred = sum(cost.finish_plan_time_s(row[2], f, nb, eng.spamm_ctx.cost_coeffs)
               for phase, s in steps if phase == "prefill"
               for v in np.asarray(s.values, np.float64).reshape(
                   -1, len(s.layout), 2)
               for row, (f, nb) in zip(s.layout, v) if row[2] is not None)
    assert st["cost_residual"]["prefill"]["predicted_s"] == \
        pytest.approx(pred, rel=1e-12)
    # the registry's binned series equal a histogram fed sample by sample
    vf = eng.obs.registry.histogram("spamm_valid_fraction", "",
                                    labelnames=("phase", "layer", "site"))
    for (phase, layer, site), (fs, _) in ref.items():
        h = Histogram("ref", buckets=vf.buckets)
        for f in fs:
            h.observe(f)
        lab = dict(phase=phase, layer=layer, site=site)
        assert vf.count(**lab) == h.count()
        assert vf.sum(**lab) == pytest.approx(h.sum(), rel=1e-12)
        for q in (0.1, 0.5, 0.9):
            assert vf.quantile(q, **lab) == pytest.approx(h.quantile(q))


def _spamm_engine(arch: str, b: int):
    """A reduced SpAMM engine, its params and an empty padded cache of b
    rows."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    eng = Engine(cfg, PCFG, make_ctx(make_host_mesh()), params, max_len=32,
                 spamm_cfg=SpammConfig(enable=True, tau=0.05, tile=4,
                                       backend="jnp"))
    cache = eng._pad_cache(M.init_cache(cfg, PCFG, b, 32), full=True)
    return eng, params, cache


def _lowered_steps(arch: str):
    """StableHLO text of a SpAMM engine's prefill, chunk and decode steps,
    lowered with the engine's frozen plans."""
    b, plen = 2, 16
    eng, params, cache = _spamm_engine(arch, b)
    toks = jnp.zeros((b, plen), jnp.int32)
    pos = jnp.zeros((b, plen), jnp.int32)
    return {
        "prefill": lambda: eng._prefill.lower(
            params, {"tokens": toks}, eng._frozen_for(b * plen)),
        "chunk": lambda: eng._chunk.lower(
            params, {"tokens": toks}, cache, pos, jnp.zeros(b, jnp.int32),
            eng._frozen_for(b * plen)),
        "decode": lambda: eng._decode.lower(
            params, toks[:, :1], cache, jnp.int32(plen), eng._frozen_for(b)),
    }


@pytest.mark.parametrize("arch,step", [
    ("musicgen-large", "prefill"), ("musicgen-large", "chunk"),
    ("musicgen-large", "decode"), ("qwen2-moe-a2.7b", "prefill"),
])
def test_compiled_steps_hold_no_host_callback(arch, step):
    """The gating telemetry leaves each compiled serving step as an output:
    no step holds a host callback (an io_callback lowers to a
    `*python_cpu_callback` custom call), MoE expert GEMMs included."""
    text = _lowered_steps(arch)[step]().as_text()
    assert "stablehlo" in text
    assert "callback" not in text


def test_step_refuses_a_cache_that_still_holds_gating_stats():
    """The engine's steps carry their gating stats out in the returned
    cache; a cache fed back into a step with them still inside is refused
    when the step traces, not carried along."""
    from repro.serving import engine as engine_mod

    eng, params, cache = _spamm_engine("musicgen-large", 2)
    _, out = eng._decode(params, jnp.zeros((2, 1), jnp.int32), cache,
                         jnp.int32(0), eng._frozen_for(2))
    assert engine_mod._STATS_KEY in out
    with pytest.raises(ValueError, match="fed back"):
        eng._decode(params, jnp.zeros((2, 1), jnp.int32), out, jnp.int32(1),
                    eng._frozen_for(2))
    steps = []
    kept = eng._keep_stats(out, "decode", steps)
    assert engine_mod._STATS_KEY not in kept and len(steps) == 1
    eng._decode(params, jnp.zeros((2, 1), jnp.int32), kept, jnp.int32(1),
                eng._frozen_for(2))


def test_spans_are_profiler_host_events(tmp_path):
    """While a profiler trace collects, a SpanTracer span and a span closed
    later by `end()` are host events of the same names that contain the
    annotations opened inside them — the device trace's clock names the
    program's spans."""
    tr = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("outer_span"):
            with jax.profiler.TraceAnnotation("inner_a"):
                jnp.ones(4).block_until_ready()
        pend = tr.begin("later_span")
        with jax.profiler.TraceAnnotation("inner_b"):
            jnp.ones(4).block_until_ready()
        pend.end(step=1)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    for outer, inner in (("outer_span", "inner_a"), ("later_span", "inner_b")):
        (o0, o1), = ev[outer]
        (i0, i1), = ev[inner]
        assert o0 <= i0 < i1 <= o1
    assert tr.span_names() == {"outer_span", "later_span"}


def _host_events(trace_dir) -> dict:
    """{name: [(start_ns, end_ns)]} of the host planes of the trace."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns)))
    return out


def test_plan_spans_fire_on_the_host_path_only(tmp_path):
    """`spamm_plan` (with its child `spamm_norms`) spans a concrete plan()
    and `spamm_execute` the execute dispatch of spamm(); tracing plan()
    under jit opens none of them."""
    from repro.core.spamm import spamm

    a = jnp.asarray(exponential_decay(128, lam=0.8, seed=0))
    b = jnp.asarray(exponential_decay(128, lam=0.8, seed=1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(spamm(a, b, 0.05, tile=32, backend="jnp"))
        jax.jit(lambda x, y: pl.plan(x, y, 0.05, tile=32,
                                     backend="jnp").valid_fraction)(a, b)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    (p0, p1), = ev["spamm_plan"]
    (n0, n1), = ev["spamm_norms"]
    (x0, x1), = ev["spamm_execute"]
    assert p0 <= n0 < n1 <= p1 <= x0 < x1


def test_engine_per_layer_on_hybrid_arch():
    """Hybrid (rec, rec, attn) stacks scan over GROUPS: layer labels are
    group indices; only the attn sub-layer carries projections but every
    sub-layer's MLP is gated — labels must stay stable and the cells must
    still sum to the aggregates."""
    cfg, eng, reqs, _ = _run_wave(arch="recurrentgemma-9b", max_new=3,
                                  plen=16, tile=16)
    sp = reqs[0].out["spamm"]
    assert sp["per_layer"], "hybrid stack lost its layer labels"
    assert all(layer >= 0 for layer in sp["per_layer"])
    _assert_cells_sum_to_aggregates(sp)
    assert eng.trace_counts == {"prefill": 1, "decode": 1}


# ---------------------------------------------------------------------------
# train loop: labeled taps under grad (custom_vjp fwd path)
# ---------------------------------------------------------------------------


def test_train_per_layer_attribution_under_grad(tmp_path):
    from repro.configs.base import TrainConfig
    from repro.train.loop import train

    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    tcfg = TrainConfig(total_steps=2, warmup=1, ckpt_every=0,
                       ckpt_dir=str(tmp_path))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="jnp")
    res = train(cfg, PCFG, tcfg, ctx, global_batch=2, seq_len=32,
                spamm_cfg=sc, log_every=0)
    assert len(res.spamm_stats) == 2
    for s in res.spamm_stats:
        per = s["per_layer"]
        assert set(per) == set(range(cfg.num_layers))
        # the scan ys carry (sum, count) per layer: the count-weighted mean
        # of the layer fractions IS the aggregate fraction
        tot = sum(c["gated_gemms"] for c in per.values())
        assert tot == s["gated_gemms"]
        mean = sum(c["valid_fraction"] * c["gated_gemms"]
                   for c in per.values()) / tot
        assert mean == pytest.approx(s["valid_fraction"], rel=1e-6)
    # the loop's own telemetry: one timed span + histogram sample per step
    assert isinstance(res.obs, Observability)
    assert res.obs.registry.histogram("train_step_seconds").count() == 2
    assert "train_step" in res.obs.tracer.span_names()
    # hard-off train run: same export shape, no spans
    res0 = train(cfg, PCFG, tcfg, ctx, global_batch=2, seq_len=32,
                 spamm_cfg=sc, log_every=0, obs=False)
    assert res0.obs.tracer.events == []
    assert res0.spamm_stats[0]["per_layer"].keys() == per.keys()


# ---------------------------------------------------------------------------
# the 4-device sharded contract (subprocess: fake host devices)
# ---------------------------------------------------------------------------

CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ParallelConfig, SpammConfig, get_config
from repro.core import schedule as S
from repro.launch.mesh import make_ctx, make_host_mesh
from repro.models import model as M
from repro.serving.engine import Engine, Request

assert len(jax.devices()) == 4, jax.devices()

pcfg = ParallelConfig(
    compute_dtype="float32", param_dtype="float32", remat="none",
    attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=32,
    decode_seq_shard=False,
)
cfg = get_config("musicgen-large").reduced()
ctx = make_ctx(make_host_mesh())
params = M.init_params(cfg, pcfg, jax.random.key(0))

TILE = 4
sc = SpammConfig(enable=True, tau=0.5, tile=TILE, backend="jnp")
rcfg = S.ReshardConfig(num_devices=4, every=2, drift_threshold=1.2,
                       probe_window=32)
eng = Engine(cfg, pcfg, ctx, params, max_len=64, spamm_cfg=sc,
             reshard_cfg=rcfg, mesh_devices=4)

rng = np.random.default_rng(0)
plen, max_new = 32, 6
prompts = [rng.integers(1, cfg.vocab, plen).astype(np.int32)
           for _ in range(16)]
reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
eng.generate(reqs)
sp = reqs[0].out["spamm"]

# layer labels survive shard_map: every scanned layer present, and the
# per-cell sums reproduce the (per-shard-scaled) wave aggregates exactly
assert set(sp["per_layer"]) == set(range(cfg.num_layers)), sp["per_layer"]
cells = [c for sites in sp["per_layer"].values() for c in sites.values()]
assert sum(c["gated_gemms"] for c in cells) == sp["gated_gemms"]
assert sum(c["decode_gated_gemms"] for c in cells) == \
    sp["decode_gated_gemms"]
# every shard returns its own rows: counts are divisible by the shard count
assert sp["gated_gemms"] % 4 == 0, sp["gated_gemms"]

# telemetry adds no traces in sharded mode either
assert eng.trace_counts == {"prefill": 1, "decode": 1}, eng.trace_counts

# the per-shard stats are step outputs: no compiled step holds a callback
b = eng._shard["perm"].size          # the live wave's padded slots
cache = jax.tree.map(lambda t: jnp.broadcast_to(t[None], (4,) + t.shape),
                     eng._pad_cache(M.init_cache(cfg, pcfg, b // 4, 64)))
texts = [eng._prefill.lower(params, {"tokens": jnp.zeros((b, plen), jnp.int32)},
                            eng._sharded_frozen_for(plen)).as_text(),
         eng._decode.lower(params, jnp.zeros((b, 1), jnp.int32), cache,
                           jnp.int32(plen), eng._sharded_frozen_for(1)).as_text()]
assert all("stablehlo" in t and "callback" not in t for t in texts)

# latency + cost channels populated; reshard history published to registry
assert sp["latency"]["ttft_s"] > 0.0
assert sp["latency"]["decode_steps"] == max_new - 1
reg = eng.obs.registry
assert reg.counter("spamm_reshard_probes_total").value() >= 1
assert {"freeze", "plan_assembly", "prefill", "decode_step",
        "reshard_probe", "wave"} <= eng.obs.tracer.span_names()
import json
json.dumps(eng.obs.tracer.chrome_trace())

print("OBS-SHARDED-OK", sp["gated_gemms"])
"""


@pytest.mark.slow
def test_sharded_engine_per_layer_telemetry_4dev():
    out = run_subprocess(CODE, devices=4)
    assert "OBS-SHARDED-OK" in out
