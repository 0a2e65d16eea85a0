"""Batched serving engine: prefill + greedy decode with slot-based batching.

Two data planes share the jitted decode step:

* WAVE mode (default for equal-length prompts): the batch prefills in one
  shot, then decodes in lockstep until every sequence finishes — a slot
  that emits EOS stays in the batch as dead weight until the wave drains.
* CHUNKED mode (`prefill_chunk`, and the automatic path for mixed-length
  prompts on attention stacks): a power-of-two-bucketed pool of slots;
  prompts prefill in tile-aligned chunks at ONE static chunk shape,
  interleaved with decode steps, writing into the KV cache at per-slot
  position offsets. Here the continuous-batching story is real: a slot
  frees when its sequence emits EOS or hits max_new_tokens, and queued
  requests are admitted into freed slots between decode steps via chunked
  prefill — no prompt is ever trimmed and per-step latency is bounded by
  the chunk size.

The decode step is the same jitted fn the dry-run lowers — decode caches
come back from prefill (wave mode pads them to the engine's max length;
chunked mode allocates full-length linear caches up front).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import cost as _cost
from repro.core import module as spmod
from repro.core import schedule as _schedule
from repro.core.plan import _bucket
from repro.models import model as M
from repro.models.transformer import NetCtx, stack_kinds
from repro.obs import (FRACTION_BUCKETS, Histogram, LATENCY_BUCKETS_S,
                       Observability)

# queue-depth / occupancy histograms bucket on a request-count ladder
# The engine's compiled steps keep a two-output form, (cache, logits) for
# prefill and chunk and (logits, cache) for decode, which the benchmark's
# fault-injection tests wrap. The model step's third output, its gating
# stats, rides the returned cache under this key until `_keep_stats` takes
# it out on the host.
_STATS_KEY = "spamm_stats"


def _two_outputs(step, cache_in: Optional[int] = None):
    """The engine's two-output form of a model step, whose gating stats (the
    step's third output, when not None) join its returned cache under
    `_STATS_KEY`. A cache that still holds them entering the step as
    argument `cache_in` is refused when the step traces."""
    def two(*args):
        if cache_in is not None and _STATS_KEY in args[cache_in]:
            raise ValueError("a step's gating stats were fed back into a "
                             "step with its cache")
        first, second, stats = step(*args)
        if stats is None:
            return first, second
        if isinstance(first, dict):
            return dict(first, **{_STATS_KEY: stats}), second
        return first, dict(second, **{_STATS_KEY: stats})

    return two


COUNT_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)


def _floor_pow2(n: int) -> int:
    """Largest power of two <= n (n >= 1). The slot pool floors a
    non-power-of-two `max_slots` so the documented cap on concurrent slots
    (and their KV-cache memory) is never exceeded while the pool stays on
    the power-of-two bucket ladder."""
    return 1 << (int(n).bit_length() - 1)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out: Optional[dict] = None   # populated by Engine.generate: per-request
                                 # metadata — {"tokens": np.ndarray,
                                 # "spamm": gating stats dict or None}


class Engine:
    """`spamm_cfg` (SpammConfig or SpammContext) turns on norm-gated GEMMs in
    prefill AND decode. The engine owns ONE SpammContext threaded through
    every request.

    Chunked prefill + slot admission (`prefill_chunk`, `max_slots`): with
    `prefill_chunk=C` (or automatically for mixed-length prompts on
    attention stacks when `prefill_chunk is None`), `generate` runs the
    slot scheduler instead of the one-shot wave. The slot pool is bucketed
    to a power of two (`cost.bucket`, capped by `max_slots`), so the
    chunked-prefill and decode jit caches are keyed by the BUCKET ladder,
    not by every distinct (batch, prompt_len) — a mixed-shape sweep
    compiles O(log slots) traces (`cost.bucket_ladder` names the bound and
    `trace_counts` proves it). Each scheduler iteration admits queued
    requests into idle slots, advances every prefilling slot by one
    tile-aligned chunk of C tokens (ONE static (slots, C) shape, written
    into full-length linear KV caches at per-slot position offsets via
    drop-mode scatters — idle/pad slots carry position sentinels ≥ max_len
    so their writes vanish), then runs one decode step over the decoding
    slots (per-row positions). Finished slots free between decode steps.
    Bit-parity contract: chunk cuts fall on row-tile boundaries
    (C % tile == 0), so on tile-aligned equal-length prompts the chunked
    tokens are bit-identical to the one-shot wave's — fully masked KV
    blocks are bitwise neutral in the online softmax, and tile membership
    (hence the gate) is unchanged. Recurrent stacks (ssm/hybrid) cannot
    chunk (state does not checkpoint at a chunk boundary): they reject
    mixed-length batches loudly instead of silently trimming. In
    pod-sharded mode `prefill_chunk` swaps the wave's one-shot prefill for
    a chunk loop at the same static shard shapes (equal lengths still
    required; admission stays wave-based).

    Frozen-plan contract (the amortization story): the weight-side gating
    artifacts are a pure function of the static weights, so the engine
    freezes them ONCE (`repro.plans.freeze_tree`, optionally warm-started
    from an on-disk `PlanStore` populated by `repro.launch.precompute_plans`
    — then engine start-up is a pure load, no planning pass) and passes the
    per-shape `FrozenPlan` pytrees into the jitted `_prefill`/`_decode` as
    ARGUMENTS. Inside the compiled graphs only the activation-side gate is
    traced; the weight get-norm and the dense-bitmap + `spamm_compact_ref`
    sort never appear — the concrete `SpammWork` work-list path (PR 3) is
    the only executed path, bit-identical to the eager plan/execute
    pipeline. `WeightPlanCache` is the in-memory tier above the store (it
    memoizes the frozen artifacts by weight fingerprint) and still serves
    the eager plan/execute path (benchmarks/plan_cache.py).

    `freeze_plans=False` opts back into the legacy in-trace gating for A/B
    comparisons (benchmarks/frozen_prefill.py measures the gap).

    Pod-sharded execution (`mesh_devices=N > 1`): the compiled steps run
    under `shard_map` over a 1-D "rows" mesh of the first N devices —
    params REPLICATED (`P()`), activation rows, decode caches, and frozen
    plans SHARDED on the leading dim (`P("rows")`). The live equal-work
    offsets drive placement: the wave's requests are cut into contiguous
    per-device groups (`schedule.rescale_offsets` maps the controller's
    probe-grid cut onto the request-group grid; `schedule.strip_tables` —
    the same construction `distributed.spamm_rowpart` shards with — builds
    the clamp-padded slot tables), and each shard's step tables come from
    `FrozenWeight.slice_rows`/`shard_by_offsets`, sliced ON HOST at
    (re-)shard time and passed as per-shard jit inputs, never in-trace.
    Every shard pads to one static width (`shard_max_width` groups, default
    2·ceil(G/N)), and strips beyond a shard's real width carry a clear
    `real` bit — pad rows do zero gated work, which is exactly how unequal
    predicted work becomes equal wall-clock. A `ReshardController` re-cut
    between decode steps swaps the live sharding WITHOUT recompiling: the
    engine keeps a per-offsets-table cache of sharded `FrozenPlan` pytrees
    (same static shapes, new table contents), re-gathers the stacked decode
    cache host-side along the slot permutation, and the jit cache hits
    (`Engine.trace_counts` proves it). Bit-parity contract: shard cuts fall
    on request-group boundaries of `tile` requests (gating is per row tile,
    so a cut inside a tile would change tile membership and the gate), and
    prompts must satisfy plen % tile == 0 — under those alignment rules the
    sharded engine's tokens are bit-identical to the single-device engine's.
    The body runs with a mesh-free `NetCtx` (ctx.shard no-ops inside the
    shard), so MoE archs — whose expert FFNs open their OWN shard_map over
    the outer mesh — are rejected at construction; per-expert frozen plans
    are the ROADMAP item that lifts this. Multi-host serving rides the same
    contract (the mesh becomes multi-host; the host-side slicing is
    device-count-agnostic) and is the remaining slice.

    Drift-triggered re-sharding (`reshard_cfg`, a `schedule.ReshardConfig`):
    the engine owns a `schedule.ReshardController` holding the equal-work
    row partition a pod deployment would feed to
    `distributed.spamm_rowpart(offsets=...)`. Every `reshard_cfg.every`
    engine steps (prefill counts one, each decode step one, cumulative
    across waves) it re-probes the coarse V estimate — activation-side
    norms of the live token embeddings, weight side piggybacking on the
    cached `WeightPlanCache.weight_side` pyramid of the probe weight (the
    unembed kernel: present for every arch, shaped like every gated GEMM's
    weight side) — and re-cuts the strips only when the live partition's
    predicted imbalance drifts beyond the fresh cut's by the configured
    threshold. Pure control plane: outputs are bit-identical with
    re-sharding on, off, or at any cadence; `Request.out["spamm"]` reports
    the wave's `resharded` event count, probe count, and the live
    partition's predicted imbalance.

    Telemetry (`obs`, a `repro.obs.Observability` bundle): the engine feeds
    three sinks. (1) The METRICS REGISTRY gets labeled samples of the gating
    stats every compiled step returns as an output (`module.StepStats`:
    per layer and site, the executed GEMM's valid fraction and bytes moved)
    — valid-fraction histograms and GEMM/byte counters keyed (phase, layer,
    site[, dtype]) — plus TTFT and per-decode-step latency histograms,
    wave/token counters, plan-cache and plan-store hit/miss counters, and
    the `ReshardController`'s probe history; `Observability.write_metrics`
    dumps it in Prometheus text form. The step stats stay on the device
    until the wave's last token and are fetched once (`wave_close`): no
    host callback and no extra sync inside a step. (2) The SPAN TRACER
    records host wall-clock spans (freeze, plan_assembly, wave, wave_open,
    prefill, prefill_chunk, decode_step, wave_close, reshard_probe,
    cache_permute), each also a profiler host event, exportable as
    Chrome-trace JSON for Perfetto. (3) The COST-RESIDUAL channel pairs
    each phase's roofline-predicted seconds (static terms captured at trace
    time, finished on the host from the returned stats) with measured
    wall-clock into a log2-ratio histogram — the live calibration check on
    the cost model the autotuner and the re-sharder both lean on.
    `obs=False` turns off spans, latency reads, the registry feed and the
    cost channel; the gating stats in `Request.out["spamm"]` are the same
    either way. Stats ride the steps as outputs with static labels, so jit
    cache keys and `trace_counts` are unchanged by instrumentation.
    """

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig, ctx: NetCtx,
                 params, *, max_len: int = 512, spamm_cfg=None,
                 plan_store=None, freeze_plans: Optional[bool] = None,
                 reshard_cfg: Optional[_schedule.ReshardConfig] = None,
                 mesh_devices: int = 0,
                 shard_max_width: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 obs=None):
        self.cfg, self.pcfg, self.ctx = cfg, pcfg, ctx
        self.params = params
        self.max_len = max_len
        self.spamm_ctx = spmod.as_context(spamm_cfg)
        enabled = self.spamm_ctx is not None and self.spamm_ctx.enable
        # f32 compute means f32 dots: the steps trace at HIGHEST. At DEFAULT
        # a TPU runs an f32 dot as one bf16 pass, and XLA hoists bf16 copies
        # of every layer's weights out of the layer scan (4.85 GB at
        # musicgen-large, which then no longer fits one v5e beside its f32
        # weights and KV cache)
        self._matmul_precision = (
            "highest" if pcfg.compute_dtype == "float32" else None)
        # `prefill_chunk`: None = auto (chunked scheduler only for
        # mixed-length attention-stack batches), int C = always chunk at C
        # tokens, 0/False = never chunk (mixed lengths are rejected).
        # `max_slots` caps the chunked scheduler's concurrent slot pool —
        # below the batch size it exercises queue-driven admission.
        self._prefill_chunk = prefill_chunk
        self._max_slots = int(max_slots) if max_slots else None
        if self._max_slots is not None and self._max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if prefill_chunk:
            c = int(prefill_chunk)
            if c < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1 (or 0/None), got "
                    f"{prefill_chunk}")
            if stack_kinds(cfg) != "attn":
                raise ValueError(
                    f"chunked prefill needs a stateless-FFN attention stack "
                    f"(got {stack_kinds(cfg)!r}: recurrent prefill state "
                    f"does not checkpoint at a chunk boundary)")
            if enabled and c % self.spamm_ctx.cfg.tile:
                raise ValueError(
                    f"prefill_chunk={c} must be a multiple of the SpAMM "
                    f"tile ({self.spamm_ctx.cfg.tile}): gating is per row "
                    f"tile, so a chunk cut inside a tile would change tile "
                    f"membership and the gate")
        # `obs`: an Observability bundle to share (CLI passes one so the
        # exported dump covers the whole run), None for a private enabled
        # bundle, False for hard-off (no spans, no latency reads, no
        # registry feed, no cost channel)
        self.obs = Observability.ensure(obs, process_name="repro-engine")
        if isinstance(plan_store, str):
            from repro.plans.store import PlanStore  # deferred: optional dep

            plan_store = PlanStore(plan_store)
        self.plan_store = plan_store
        self._freeze = enabled if freeze_plans is None else (
            bool(freeze_plans) and enabled)
        if enabled and plan_store is not None:
            self.spamm_ctx.cache.store = plan_store
        self._fw_tree = None     # path-tree of FrozenWeight (lists per layer)
        self._fp_cache: dict = {}  # row-tile grid gm → FrozenPlan pytree
        self._sfp_cache: dict = {}  # (tpg, width, offsets) → sharded pytree
        self._gm_hist: dict = {}   # observed row-tile grid gm → step count
        self._resharder = None
        self._steps = 0          # engine steps (prefill + decode), all waves
        self._shard = None       # live wave's sharding tables (sharded mode)
        self.trace_counts = {"prefill": 0, "decode": 0}  # (re)compile guard
        # the last wave's prefill logits and, when sharded, the slots of its
        # real requests (`first_logits` gathers them on read)
        self._first = None
        self._ndev = int(mesh_devices) if mesh_devices else 0
        self._sharded = self._ndev > 1
        self._shard_width = shard_max_width
        if self._sharded:
            if not self._freeze:
                raise ValueError(
                    "mesh_devices > 1 needs frozen plans (per-shard step "
                    "tables ARE the sharding mechanism) — enable spamm_cfg "
                    "and keep freeze_plans on")
            if cfg.moe is not None:
                raise ValueError(
                    "pod-sharded serving cannot take MoE archs yet: expert "
                    "FFNs open their own shard_map over the outer mesh "
                    "(per-expert frozen plans are the ROADMAP item)")
            devs = jax.devices()
            if len(devs) < self._ndev:
                raise ValueError(
                    f"mesh_devices={self._ndev} but only {len(devs)} "
                    f"devices visible")
            from jax.sharding import Mesh

            self._spamm_mesh = Mesh(np.array(devs[:self._ndev]), ("rows",))
        if reshard_cfg is not None and enabled and reshard_cfg.every > 0:
            if self._sharded and reshard_cfg.num_devices == 0:
                reshard_cfg = dataclasses.replace(
                    reshard_cfg, num_devices=self._ndev)
            else:
                reshard_cfg = _schedule.resolve_reshard_devices(
                    reshard_cfg, ctx.mesh, ctx.batch_axes)
            if self._sharded and reshard_cfg.num_devices != self._ndev:
                raise ValueError(
                    f"reshard_cfg cuts {reshard_cfg.num_devices} strips but "
                    f"the engine shards over {self._ndev} devices — they "
                    f"must match (the cut IS the placement)")
            self._resharder = _schedule.ReshardController(reshard_cfg)
        if enabled and self._freeze and self.obs.enabled:
            # arm the cost-prediction tap channel BEFORE the first trace:
            # coefficients resolve once, host-side, from the tune profile
            # (or the nominal table) at the config's resolved backend
            from repro.kernels.ops import resolve_backend

            scfg = self.spamm_ctx.cfg
            prof = _cost.CostProfile.load_or_default(
                getattr(scfg, "tune_profile", None))
            self.spamm_ctx.enable_cost_taps(
                prof.coeffs(resolve_backend(scfg.backend)))
        if self.obs.enabled:
            reg = self.obs.registry
            self._m_ttft = reg.histogram(
                "serve_ttft_seconds", labelnames=(),
                help="wave start to first-token available (includes reshard "
                     "probe + prefill dispatch + execution)",
                buckets=LATENCY_BUCKETS_S)
            self._m_decode_s = reg.histogram(
                "serve_decode_step_seconds", labelnames=(),
                help="inter-token latency per decode step (reshard stalls "
                     "included)", buckets=LATENCY_BUCKETS_S)
            self._m_vf = reg.histogram(
                "spamm_valid_fraction", labelnames=("phase", "layer", "site"),
                help="per-execution gated-GEMM valid fraction",
                buckets=FRACTION_BUCKETS)
            self._m_gemms = reg.counter(
                "spamm_gated_gemms_total",
                labelnames=("phase", "layer", "site"),
                help="gated GEMM executions (per shard in sharded mode)")
            self._m_bytes = reg.counter(
                "spamm_gemm_bytes_total",
                labelnames=("phase", "layer", "site", "dtype"),
                help="analytic GEMM bytes moved by the executed work-lists")
            self._m_waves = reg.counter(
                "serve_waves_total", help="request waves served")
            self._m_tokens = reg.counter(
                "serve_tokens_total", help="tokens emitted")
            self._m_cache = reg.counter(
                "spamm_plan_cache_total", labelnames=("result",),
                help="WeightPlanCache hits/misses")
            self._m_store = reg.counter(
                "spamm_plan_store_total", labelnames=("result",),
                help="on-disk PlanStore hits/misses")
            self._m_admit = reg.counter(
                "serve_admissions_total",
                help="requests admitted into a slot (chunked scheduler)")
            self._m_chunks = reg.counter(
                "serve_prefill_chunks_total",
                help="chunked-prefill steps executed (each advances every "
                     "prefilling slot by prefill_chunk tokens)")
            self._m_queue = reg.histogram(
                "serve_queue_depth", labelnames=(),
                help="requests waiting for a slot, sampled per scheduler "
                     "iteration (chunked mode)", buckets=COUNT_BUCKETS)
            self._m_occupancy = reg.histogram(
                "serve_slot_occupancy", labelnames=(),
                help="live slots per scheduler iteration (chunked mode)",
                buckets=COUNT_BUCKETS)
        self._build_steps()

    def _counted(self, fn, key: str):
        """Wrap a step body so Python re-execution (= a fresh jit trace)
        bumps `trace_counts[key]` — the recompile-free re-shard guard — and
        traces at the engine's matmul precision."""
        prec = self._matmul_precision

        def wrapped(*args):
            self.trace_counts[key] += 1
            if prec is None:
                return fn(*args)
            with jax.default_matmul_precision(prec):
                return fn(*args)

        return wrapped

    def _build_steps(self):
        cfg, pcfg = self.cfg, self.pcfg
        chunkable = stack_kinds(cfg) == "attn"
        if not self._sharded:
            self._prefill = jax.jit(self._counted(_two_outputs(
                M.make_prefill_step(cfg, pcfg, self.ctx,
                                    spamm_cfg=self.spamm_ctx)), "prefill"))
            self._decode = jax.jit(self._counted(_two_outputs(
                M.make_decode_step(
                    cfg, pcfg, self.ctx,
                    spamm_cfg=self.spamm_ctx if self._freeze else None),
                cache_in=2), "decode"))
            # chunked prefill shares the "prefill" trace counter: the
            # jit-cache-bound guard counts every prefill-side trace
            self._chunk = None if not chunkable else jax.jit(self._counted(
                _two_outputs(M.make_prefill_chunk_step(
                    cfg, pcfg, self.ctx, spamm_cfg=self.spamm_ctx),
                    cache_in=2), "prefill"))
            return
        from jax.sharding import PartitionSpec as P

        # the body computes one shard locally: a mesh-free ctx makes every
        # ctx.shard a no-op (no nested sharding constraints), and the frozen
        # plans / caches arrive with a leading shard dim that the body peels
        body_ctx = NetCtx(mesh=None, batch_axes=(),
                          model_axis=self.ctx.model_axis)
        inner_pre = M.make_prefill_step(cfg, pcfg, body_ctx,
                                        spamm_cfg=self.spamm_ctx)
        inner_dec = M.make_decode_step(cfg, pcfg, body_ctx,
                                       spamm_cfg=self.spamm_ctx)

        def unstack(tree):
            return jax.tree.map(lambda t: t[0], tree)

        def restack(tree):
            return jax.tree.map(lambda t: t[None], tree)

        def pre_body(params, batch, frozen):
            cache, logits, stats = inner_pre(params, batch, unstack(frozen))
            return restack(cache), logits, restack(stats)

        def dec_body(params, inp, cache, pos, frozen):
            logits, cache, stats = inner_dec(params, inp, unstack(cache), pos,
                                             unstack(frozen))
            return logits, restack(cache), restack(stats)

        mesh = self._spamm_mesh
        self._prefill = jax.jit(jax.shard_map(
            self._counted(_two_outputs(pre_body), "prefill"), mesh=mesh,
            in_specs=(P(), P("rows"), P("rows")),
            out_specs=(P("rows"), P("rows"))))
        self._decode = jax.jit(jax.shard_map(
            self._counted(_two_outputs(dec_body, cache_in=2), "decode"),
            mesh=mesh,
            in_specs=(P(), P("rows"), P("rows"), P(), P("rows")),
            out_specs=(P("rows"), P("rows"))))
        self._chunk = None
        if chunkable:
            inner_chunk = M.make_prefill_chunk_step(
                cfg, pcfg, body_ctx, spamm_cfg=self.spamm_ctx)

            def chunk_body(params, batch, cache, positions, last_idx,
                           frozen):
                cache, logits, stats = inner_chunk(
                    params, batch, unstack(cache), positions, last_idx,
                    unstack(frozen))
                return restack(cache), logits, restack(stats)

            self._chunk = jax.jit(jax.shard_map(
                self._counted(_two_outputs(chunk_body, cache_in=2),
                              "prefill"), mesh=mesh,
                in_specs=(P(), P("rows"), P("rows"), P("rows"), P("rows"),
                          P("rows")),
                out_specs=(P("rows"), P("rows"))))

    @property
    def first_logits(self):
        """(B, vocab) f32: the logits the last wave read its first tokens
        from, in request order — what agreement checks compare against a
        reference engine. None before a wave and after a chunked run, whose
        slots finish prefill in different steps."""
        if self._first is None:
            return None
        logits, slots = self._first
        return logits if slots is None else logits[jnp.asarray(slots)]

    # -- drift-triggered re-sharding (control plane) -------------------------
    @property
    def partition_offsets(self):
        """Live equal-work row-offset table (None until the first probe) —
        what a pod deployment passes to `distributed.spamm_rowpart`."""
        return self._resharder.offsets if self._resharder else None

    @property
    def shard_layout(self):
        """Live wave layout in REQUEST units — None when unsharded or
        before the first wave. `offsets` cuts the batch into per-shard
        request ranges; `slot_width` is the padded per-shard slot count
        every shard allocates; `real` the per-shard live request counts."""
        if not self._sharded or self._shard is None:
            return None
        tile = self.spamm_ctx.cfg.tile
        offs = self._shard["offs_g"] * tile
        return {"offsets": offs,
                "slot_width": int(self._shard["wmax_g"]) * tile,
                "real": [int(r) for r in np.diff(offs)]}

    def _maybe_reshard(self, requests, outs, cache=None, cur=None):
        """Advance the engine step counter; at the configured cadence,
        re-probe the coarse work estimate from the live tokens (prompts +
        generated so far) and let the controller re-cut on drift
        (`model.reshard_probe` is the shared probe body). Never touches the
        computed values. In pod-sharded mode a re-cut additionally swaps
        the live wave's tables and re-gathers `cache`/`cur` host-side along
        the slot permutation — same static shapes and shardings, so the
        jitted steps' cache entries survive (`trace_counts` proves it).
        Returns the (possibly re-gathered) `(cache, cur)`."""
        step, self._steps = self._steps, self._steps + 1
        rs = self._resharder
        if rs is None or not rs.due(step):
            return cache, cur
        win = rs.cfg.probe_window
        # per-request most-recent window keeps probe cost constant as
        # generation grows (the estimate tracks the live distribution; the
        # distant past doesn't shard the next step's rows anyway)

        def recent(r, o):
            t = np.concatenate([np.asarray(r.prompt, np.int64),
                                np.asarray(o, np.int64)])
            return t[-win:] if win else t

        toks = np.concatenate([recent(r, o)
                               for r, o in zip(requests, outs)])
        with self.obs.span("reshard_probe", step=step):
            M.reshard_probe(rs, self.spamm_ctx, self.params, step,
                            tokens=toks)
        if self._sharded and self._shard is not None:
            src = self._refresh_shard()
            if src is not None:
                with self.obs.span("cache_permute", step=step):
                    if cache is not None:
                        cache = self._permute_cache(cache, src)
                    if cur is not None:
                        from jax.sharding import NamedSharding
                        from jax.sharding import PartitionSpec as P

                        cur = jax.device_put(
                            jnp.take(cur, jnp.asarray(src), axis=0),
                            NamedSharding(self._spamm_mesh, P("rows")))
        if self.obs.enabled and rs is not None:
            rs.publish(self.obs.registry)
        return cache, cur

    # -- frozen-plan assembly ------------------------------------------------
    def _frozen_for(self, rows: int) -> dict:
        """The FrozenPlan pytree for a step whose gated GEMMs see `rows`
        flattened activation rows — built once per row-tile grid and reused
        (the jitted steps recompile per shape anyway, so this adds no
        compiles). Stacked layers get stacked plans (scan xs)."""
        if not self._freeze:
            return {}
        scfg = self.spamm_ctx.cfg
        tile = scfg.tile
        gm = (rows + tile - 1) // tile
        hit = self._fp_cache.get(gm)
        if hit is not None:
            return hit
        self._ensure_fw_tree()
        with self.obs.span("plan_assembly", gm=gm):
            return self._assemble_frozen(gm)

    def _assemble_frozen(self, gm: int) -> dict:
        from repro.plans.frozen import stack_plans

        def specialize(node):
            if isinstance(node, dict):
                return {k: specialize(v) for k, v in node.items()}
            if isinstance(node, list):
                # per-layer plans must share one step bucket to stack into a
                # scan input; padding steps carry a clear `real` bit. Each
                # weight's autotuned bucket floor participates in the max, so
                # the common bucket honors every layer's tuned floor (the
                # result is a power of two ≥ each floor, hence stable under
                # every layer's own for_rows flooring).
                bucket = max(_bucket(gm * fw.num_kj, fw.bucket_floor)
                             for fw in node)
                return stack_plans(
                    [fw.for_rows(gm, min_steps=bucket) for fw in node])
            return node.for_rows(gm)

        tree = specialize(self._fw_tree)
        self._fp_cache[gm] = tree
        return tree

    def _ensure_fw_tree(self):
        """Freeze the weight-side gating artifacts once (warm-started from
        the plan store when present) — shared by the single-device and
        pod-sharded assembly paths."""
        if self._fw_tree is None:
            from repro.plans.precompute import freeze_tree

            with self.obs.span("freeze",
                               store=self.plan_store is not None):
                self._fw_tree, _ = freeze_tree(
                    self.params, self.spamm_ctx.cfg,
                    cache=self.spamm_ctx.cache, store=self.plan_store)

    def _note_gm(self, gm: int, n: int = 1):
        self._gm_hist[int(gm)] = self._gm_hist.get(int(gm), 0) + int(n)

    @property
    def gm_histogram(self) -> dict:
        """Observed serving row-grid histogram {gm row tiles: executed gated
        step count}. Feed it to `core.cost.tune_weight(gm_hist=...)` so the
        tuner prices the grids this engine actually runs instead of the
        synthetic `DEFAULT_TUNE_GM`."""
        return dict(self._gm_hist)

    # -- pod-sharded wave layout ---------------------------------------------
    def _group_offsets(self, G: int, wmax_g: int) -> np.ndarray:
        """The live cut re-expressed on the wave's request-group grid and
        clamped to the static shard width (uniform until the first probe)."""
        rs = self._resharder
        src = (np.asarray(rs.offsets, np.int64)
               if rs is not None and rs.offsets is not None
               else np.arange(self._ndev + 1, dtype=np.int64))
        return _schedule.rescale_offsets(src, G, max_width=wmax_g)

    def _shard_tables(self, offs_g: np.ndarray, wmax_g: int, G: int) -> dict:
        """Request-level gather tables for one cut: `perm` lists, per padded
        slot in (device, slot) order, the request that fills it (pad slots
        clamp-replicate their strip's last group, so every slot carries live
        data and no garbage feeds the tile gates); `keep` marks real slots;
        `real_slots[r]` is the unique kept slot holding request r."""
        tile = self.spamm_ctx.cfg.tile
        perm_g, keep_g = _schedule.strip_tables(
            offs_g, G, self._ndev, width=wmax_g)
        perm = (perm_g[:, None] * tile + np.arange(tile)).reshape(-1)
        keep = np.repeat(keep_g, tile)
        slots = np.nonzero(keep)[0]
        real = np.empty(G * tile, np.int64)
        real[perm[slots]] = slots
        return {"G": int(G), "wmax_g": int(wmax_g),
                "offs_g": np.asarray(offs_g, np.int64),
                "perm": perm, "keep": keep, "real_slots": real}

    def _begin_wave(self, b: int, plen: int):
        """Lay a wave out on the mesh: cut the request groups by the live
        offsets and pin the static per-shard width for the whole wave, so a
        mid-wave re-cut can never change a shape."""
        tile = self.spamm_ctx.cfg.tile
        ndev = self._ndev
        if b % tile:
            raise ValueError(
                f"pod-sharded serving needs batch % tile == 0 (got b={b}, "
                f"tile={tile}): gating is per row tile, and a shard cut "
                f"inside a tile would change tile membership and the gate")
        if plen % tile:
            raise ValueError(
                f"pod-sharded serving needs prompt length % tile == 0 (got "
                f"plen={plen}, tile={tile}) so prefill row tiles never "
                f"straddle a request boundary")
        G = b // tile
        if G < ndev:
            raise ValueError(
                f"{G} request group(s) of tile={tile} requests cannot fill "
                f"{ndev} shards — grow the batch to at least tile*ndev="
                f"{tile * ndev}")
        ceil_g = -(-G // ndev)
        cap = int(self._shard_width) if self._shard_width else 2 * ceil_g
        wmax_g = max(ceil_g, min(G, cap))
        self._shard = self._shard_tables(
            self._group_offsets(G, wmax_g), wmax_g, G)

    def _refresh_shard(self):
        """Re-cut the live wave from the controller's current offsets.
        Returns the old→new global-slot gather, or None when the cut (at
        request-group granularity) did not move."""
        sh = self._shard
        offs_g = self._group_offsets(sh["G"], sh["wmax_g"])
        if np.array_equal(offs_g, sh["offs_g"]):
            return None
        new = self._shard_tables(offs_g, sh["wmax_g"], sh["G"])
        src = sh["real_slots"][new["perm"]]
        self._shard = new
        return src

    def _sharded_frozen_for(self, tpg: int) -> dict:
        """Per-shard FrozenPlan pytree for the live cut, stacked on a
        leading mesh dim — `tpg` is row tiles per request group (plen for
        prefill, 1 for decode). Sliced ON HOST from the frozen weight-side
        tables and cached per (tpg, width, offsets): a re-cut back to a
        seen cut is a dict hit, a fresh cut costs only numpy slicing, and
        either way the jitted steps never see a new shape."""
        sh = self._shard
        key = (tpg, sh["wmax_g"], tuple(int(x) for x in sh["offs_g"]))
        hit = self._sfp_cache.get(key)
        if hit is not None:
            return hit
        self._ensure_fw_tree()
        with self.obs.span("plan_assembly", tpg=tpg, sharded=True):
            return self._assemble_sharded(tpg, key)

    def _assemble_sharded(self, tpg: int, key) -> dict:
        sh = self._shard

        from repro.plans.frozen import stack_plans

        offs = sh["offs_g"] * tpg      # the cut, on this step's row-tile grid
        W = sh["wmax_g"] * tpg         # padded per-shard row-tile width
        ndev = self._ndev

        def specialize(node):
            if isinstance(node, dict):
                return {k: specialize(v) for k, v in node.items()}
            if isinstance(node, list):
                # same cross-layer common-bucket rule as `_frozen_for`, but
                # computed at the PADDED width so every shard — and every
                # future cut at this width — lands on one step count
                bucket = max(_bucket(W * fw.num_kj, fw.bucket_floor)
                             for fw in node)
                shards = [stack_plans([fw.slice_rows(
                    int(offs[d]), int(offs[d + 1]), gm=W, min_steps=bucket)
                    for fw in node]) for d in range(ndev)]
                return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)
            return node.shard_by_offsets(offs, width=W)

        tree = specialize(self._fw_tree)
        self._sfp_cache[key] = tree
        return tree

    def _permute_cache(self, cache, src):
        """Host-side re-gather of the stacked decode cache along the
        old→new slot map `src` (a re-cut is rare; the jitted steps never
        see this op). Leaves come back committed to the mesh with the same
        P("rows") layout the steps emit, so the swap cannot perturb the jit
        cache key."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        ndev = self._ndev
        rows = NamedSharding(self._spamm_mesh, P("rows"))
        idx = jnp.asarray(src)

        def fix(path, t):
            keys = [getattr(k, "key", None) for k in path]
            name = keys[-1] if keys else None
            # batch-axis-from-the-end suffix rule (model.cache_pspecs):
            # counting from the end survives the leading mesh-stack dim
            if name in ("k", "v", "state"):
                ba = t.ndim - 4
            elif name == "h":
                ba = t.ndim - 2
            elif name == "conv":
                ba = t.ndim - 3
            else:
                return t
            u = jnp.moveaxis(t, ba, 1)
            per = u.shape[1]
            u = u.reshape((ndev * per,) + u.shape[2:])
            u = jnp.take(u, idx, axis=0)
            u = u.reshape((ndev, per) + u.shape[1:])
            return jax.device_put(jnp.moveaxis(u, 1, ba), rows)

        return jax.tree_util.tree_map_with_path(fix, cache)

    def _pad_cache(self, cache, *, full: bool = False):
        """Grow linear KV caches to the engine's slot budget: max_len, or
        the sliding window when one is smaller (the decode ring). With
        `full=True` always grow to max_len — chunked prefill scatters at
        absolute positions, so windowed archs keep a LINEAR full-length
        cache (the window applies as a mask; `layer_decode`'s ring
        condition turns itself off on a cache longer than the window, and
        when window >= max_len keeps it True the per-row decode path still
        writes linearly — it never applies the ring modulo, so position
        sentinels drop instead of wrapping)."""
        target = (
            min(self.max_len, self.cfg.sliding_window)
            if self.cfg.sliding_window and not full else self.max_len
        )

        def grow(path, t):
            keys = [getattr(k, "key", None) for k in path]
            if keys and keys[-1] in ("k", "v") and t.shape[-3] < target:
                pad = [(0, 0)] * t.ndim
                pad[-3] = (0, target - t.shape[-3])
                return jnp.pad(t, pad)
            return t

        return jax.tree_util.tree_map_with_path(grow, cache)

    def _spamm_stats(self, steps, hits0: int, misses0: int,
                     store0: Optional[tuple], reshard0: Optional[tuple],
                     ttft_s=None, decode_lat=()):
        """Per-wave gating stats dict from the wave's `steps` — `(phase,
        StepStats)` as the compiled steps returned them, still on the
        device: fetched here in ONE transfer — and the plan-cache/plan-store
        counter DELTAS across this wave (every counter in the dict is
        per-wave: after first population a warm wave reports 0/0 store
        traffic, never stale lifetime totals). With re-sharding on,
        `resharded`/`reshard_probes` are the wave's event deltas and
        `partition_imbalance` the live partition's predicted imbalance at
        the last probe. Bytes moved are SUMS per phase: bandwidth adds up
        across GEMMs where fractions average. In pod-sharded mode every
        shard returns its own rows, so `gated_gemms` counts scale by mesh
        size and the fractions average over shards — pad tiles included,
        which is the honest number: pad steps are part of each shard's
        bucket.

        - `per_layer`: {layer: {site: {...}}} breakdown of the same values
          — fractions average and counts/bytes sum within each (layer,
          site) cell, so summing `gated_gemms` over cells reproduces the
          wave aggregate exactly.
        - `latency`: host wall-clock — `ttft_s` (wave start to first token
          materialized) and decode-step stats (mean/p50/p95 over the wave's
          measured inter-token gaps; p50/p95 are bucket-interpolated from
          a wave-local histogram with the registry's latency ladder).
        - `cost_residual`: per phase, the roofline-predicted seconds summed
          over this wave's executed gated GEMMs (÷ mesh size when sharded:
          shards run concurrently) paired with the measured wall-clock, plus
          log2(measured/predicted). Only present when the cost channel is
          armed (engine obs enabled) and both sides are positive.
        """
        host = jax.device_get([st.values for _, st in steps])
        coeffs = self.spamm_ctx.cost_coeffs
        nbk = len(FRACTION_BUCKETS) + 1
        # (phase, layer, site) -> ([fraction sum, count, bytes sum, counted
        # bytes], the fractions' bucket counts on FRACTION_BUCKETS)
        cells: dict = {}
        pred = {"prefill": 0.0, "decode": 0.0}
        groups: dict = {}
        for (phase, st), v in zip(steps, host):
            groups.setdefault((phase, st.layout), []).append(
                np.asarray(v, np.float64).reshape(-1, len(st.layout), 2))
        for (phase, layout), vs in groups.items():
            v = np.concatenate(vs)           # (executions, rows, 2)
            f, nb = v[..., 0], v[..., 1]
            keys = {}
            cid = np.asarray([keys.setdefault((phase, layer, site), len(keys))
                              for layer, site, _ in layout], np.int64)
            c = len(keys)
            counted = np.isfinite(nb)
            agg = np.stack([
                np.bincount(cid, f.sum(0), c),
                np.bincount(cid, None, c) * f.shape[0],
                np.bincount(cid, np.where(counted, nb, 0.0).sum(0), c),
                np.bincount(cid, counted.sum(0), c)], -1)
            hist = np.bincount(
                (cid * nbk + np.searchsorted(FRACTION_BUCKETS, f)).ravel(),
                None, c * nbk).reshape(c, nbk)
            for key, a, h in zip(keys, agg.tolist(), hist.tolist()):
                if key in cells:     # the phase ran at another shape too
                    a = [x + y for x, y in zip(cells[key][0], a)]
                    h = [x + y for x, y in zip(cells[key][1], h)]
                cells[key] = (a, h)
            priced = [j for j, row in enumerate(layout) if row[2] is not None]
            if coeffs is not None and priced:
                static = np.asarray([layout[j][2] for j in priced]).T
                pred[phase] += float(np.sum(_cost.finish_plan_time_s(
                    static, f[:, priced], nb[:, priced], coeffs)))

        def totals(decode: bool):
            a = np.sum([x[0] for (ph, _, _), x in cells.items()
                        if (ph == "decode") == decode] or [[0.0] * 4], 0)
            return (float(a[0] / a[1]) if a[1] else None, int(a[1]),
                    float(a[2]) if a[3] else None)

        vf, gg, nb = totals(False)
        dvf, dgg, dnb = totals(True)
        cache = self.spamm_ctx.cache
        stats = {
            "valid_fraction": vf,
            "gated_gemms": gg,
            "decode_valid_fraction": dvf,
            "decode_gated_gemms": dgg,
            "compute_dtype": getattr(self.spamm_ctx.cfg, "dtype", "float32"),
            "gemm_bytes_moved": nb,
            "decode_gemm_bytes_moved": dnb,
            "plan_cache_hits": cache.hits - hits0,
            "plan_cache_misses": cache.misses - misses0,
        }
        if store0 is not None:
            stats["plan_store_hits"] = self.plan_store.hits - store0[0]
            stats["plan_store_misses"] = self.plan_store.misses - store0[1]
        if reshard0 is not None:
            rs = self._resharder
            stats["resharded"] = rs.resharded - reshard0[0]
            stats["reshard_probes"] = rs.probes - reshard0[1]
            stats["partition_imbalance"] = rs.live_imbalance
        # -- per-(layer, site) breakdown ------------------------------------
        acc: dict = {}
        for (phase, layer, site), (a, _) in cells.items():
            acc.setdefault((layer, site), {})[phase == "decode"] = a
        per_layer: dict = {}
        zero = [0.0] * 4
        for (layer, site), by in sorted(acc.items()):
            pre, dec = by.get(False, zero), by.get(True, zero)
            nbytes = pre[2] + dec[2]
            per_layer.setdefault(layer, {})[site] = {
                "valid_fraction": pre[0] / pre[1] if pre[1] else None,
                "gated_gemms": int(pre[1]),
                "decode_valid_fraction": dec[0] / dec[1] if dec[1] else None,
                "decode_gated_gemms": int(dec[1]),
                "gemm_bytes_moved": nbytes if nbytes else None,
            }
        stats["per_layer"] = per_layer
        # -- latency ---------------------------------------------------------
        decode_lat = list(decode_lat)
        if ttft_s is not None or decode_lat:
            lat = {"ttft_s": ttft_s, "decode_steps": len(decode_lat)}
            if decode_lat:
                h = Histogram("wave_decode_step_seconds",
                              buckets=LATENCY_BUCKETS_S)
                for v in decode_lat:
                    h.observe(v)
                lat["decode_mean_s"] = float(np.mean(decode_lat))
                lat["decode_p50_s"] = h.quantile(0.5)
                lat["decode_p95_s"] = h.quantile(0.95)
            stats["latency"] = lat
        # -- cost residual ---------------------------------------------------
        if coeffs is not None:
            ndev = self._ndev if self._sharded else 1
            meas_dec = float(np.sum(decode_lat)) if decode_lat else 0.0
            cres = {}
            for phase, meas in (("prefill", ttft_s or 0.0),
                                ("decode", meas_dec)):
                p = pred[phase] / ndev
                if p > 0.0 and meas > 0.0:
                    r = self.obs.residual.record(phase, p, meas)
                    cres[phase] = {"predicted_s": p, "measured_s": meas,
                                   "log2_ratio": r}
            if cres:
                stats["cost_residual"] = cres
        # -- registry feed ---------------------------------------------------
        if self.obs.enabled:
            dtype = stats["compute_dtype"]
            self._m_vf.merge({k: (h, a[0]) for k, (a, h) in cells.items()})
            self._m_gemms.inc_many({k: a[1] for k, (a, _) in cells.items()})
            self._m_bytes.inc_many({k + (dtype,): a[2]
                                    for k, (a, _) in cells.items() if a[3]})
            self._m_cache.inc(stats["plan_cache_hits"], result="hit")
            self._m_cache.inc(stats["plan_cache_misses"], result="miss")
            if store0 is not None:
                self._m_store.inc(stats["plan_store_hits"], result="hit")
                self._m_store.inc(stats["plan_store_misses"], result="miss")
        return stats

    # -- wave layout / dispatch ----------------------------------------------
    def _default_chunk(self) -> int:
        """Tile-aligned default chunk size for the auto mixed-length path."""
        tile = (self.spamm_ctx.cfg.tile
                if self.spamm_ctx is not None and self.spamm_ctx.enable
                else 1)
        return -(-16 // tile) * tile

    def _resolve_chunk(self, mixed: bool) -> Optional[int]:
        """The chunk size this batch prefills at, or None for one-shot."""
        pc = self._prefill_chunk
        if pc is not None and not pc:      # 0/False: chunking disabled
            return None
        if pc is None:                     # auto: chunk only when needed
            if not mixed or self._sharded or self._chunk is None:
                return None
            return self._default_chunk()
        return int(pc)

    def generate(self, requests: List[Request]) -> List[np.ndarray]:
        """Greedy-decode a batch of prompts. Equal-length batches run the
        lockstep wave (one-shot prefill unless `prefill_chunk` asks for
        chunking); mixed-length batches run the chunked slot scheduler on
        attention stacks — every prompt's tokens are used in full. Batches
        the engine cannot serve faithfully raise ValueError instead of
        silently truncating: prompts longer than max_len - 1, and mixed
        lengths where chunking is unavailable (recurrent stacks,
        pod-sharded mode, or an explicit `prefill_chunk=0`).

        When SpAMM is enabled, each request's `out` metadata carries the
        gating stats of its wave, split by phase: prefill (valid_fraction /
        gated_gemms over the gated prefill GEMMs) and decode
        (decode_valid_fraction / decode_gated_gemms summed over the wave's
        decode steps), plus plan-cache hit/miss deltas, a `per_layer`
        breakdown keyed by layer index and GEMM site, `latency` (TTFT and
        decode-step wall-clock stats), and — when the cost channel is armed
        — a `cost_residual` predicted-vs-measured pairing per phase (see
        `_spamm_stats`).

        Host timing uses the lockstep loop's OWN blocking points: the loop
        top's `np.asarray(cur)` blocks on the previous step's output, so the
        engine opens a step's span at dispatch (`SpanTracer.begin`) and
        closes it at that next block — no added device sync.
        """
        assert requests, "empty batch"
        plens = [len(r.prompt) for r in requests]
        if min(plens) < 1:
            raise ValueError("empty prompt")
        if max(plens) > self.max_len - 1:
            raise ValueError(
                f"prompt of {max(plens)} tokens does not fit "
                f"max_len={self.max_len} (a sequence needs at least one "
                f"decode slot) — raise max_len instead of losing prompt "
                f"tokens")
        mixed = len(set(plens)) > 1
        chunk = self._resolve_chunk(mixed)
        if not self._sharded and chunk:
            return self._generate_chunked(requests, chunk)
        if mixed:
            # loud rejection instead of the old silent left-trim to the
            # shortest prompt: every alternative here loses prompt tokens
            if self._sharded:
                raise ValueError(
                    "pod-sharded serving needs equal-length prompts (the "
                    "chunked mixed-length scheduler is unsharded-only); "
                    "pad client-side or serve unsharded")
            if self._chunk is None:
                raise ValueError(
                    f"{stack_kinds(self.cfg)!r} stacks cannot chunk "
                    f"mixed-length prompts (recurrent prefill state does "
                    f"not checkpoint at a chunk boundary); pad client-side "
                    f"to one length")
            raise ValueError(
                "mixed-length prompts need chunked prefill, but "
                "prefill_chunk=0 disabled it; drop the override or pad "
                "client-side")
        return self._generate_wave(requests, chunk)

    def _generate_wave(self, requests: List[Request],
                       chunk: Optional[int] = None) -> List[np.ndarray]:
        """Lockstep wave: prefill the whole (equal-length) batch, decode
        until every sequence finishes. `chunk` (pod-sharded mode only —
        unsharded chunked batches take `_generate_chunked`) swaps the
        one-shot prefill for a chunk loop at one static shard shape."""
        b = len(requests)
        plen = len(requests[0].prompt)
        tracer = self.obs.tracer
        # the wave's span opens in the function that does its work, so that
        # the profiler's event of this function covers it and an idle gap
        # inside the wave reads `wave`
        with self.obs.span("wave", batch=b, prompt_len=plen):
            t_wave0 = time.perf_counter_ns()
            opening = tracer.begin("wave_open")
            toks = np.stack([r.prompt for r in requests]).astype(np.int32)
            collect = self.spamm_ctx is not None and self.spamm_ctx.enable
            obs_on = self.obs.enabled
            pend = None          # OpenSpan of a dispatched, un-blocked step
            ttft_s = None
            decode_lat: list = []
            steps: list = []  # (phase, StepStats) of the wave, on the device
            deltas = self._counters() if collect else None
            # frozen-plan assembly counts into this wave's store deltas (it is
            # where first population / warm-start loading happens)
            if self._sharded:
                self._begin_wave(b, plen)
                frozen_pre = self._sharded_frozen_for(plen)
                frozen_dec = self._sharded_frozen_for(1)
            else:
                frozen_pre = self._frozen_for(b * plen)
                frozen_dec = self._frozen_for(b) if self._freeze else {}
            tile = self.spamm_ctx.cfg.tile if collect else 0
            outs = [[] for _ in range(b)]
            self._maybe_reshard(requests, outs)
            if self._sharded:
                # the step-0 probe above may have laid down the first cut;
                # re-read the wave tables (dict hits unless the cut moved)
                # and put the batch in padded (device, slot) order
                frozen_pre = self._sharded_frozen_for(plen)
                frozen_dec = self._sharded_frozen_for(1)
                toks_in = toks[self._shard["perm"]]
            else:
                toks_in = toks
            if chunk:
                opening.end()
                pend = tracer.begin("prefill")
                cache, logits = self._sharded_chunk_prefill(
                    toks_in, plen, chunk, steps)
            else:
                batch = {"tokens": jnp.asarray(toks_in)}
                opening.end()
                pend = tracer.begin("prefill")
                cache, logits = self._prefill(self.params, batch, frozen_pre)
                cache = self._keep_stats(cache, "prefill", steps)
                if collect:
                    if self._sharded:
                        self._note_gm(self._shard["wmax_g"] * plen,
                                      self._ndev)
                    else:
                        self._note_gm(-(-(b * plen) // tile))
                cache = self._pad_cache(cache)
            self._first = (logits, self._shard["real_slots"]
                           if self._sharded else None)
            done = np.zeros(b, bool)
            cur = jnp.argmax(logits, -1).astype(jnp.int32)
            pos = plen
            budget = max(r.max_new_tokens for r in requests)
            for t in range(budget):
                vis = np.asarray(cur)   # blocks on the previous step
                if pend is not None:
                    t1 = pend.end(step=t)
                    if obs_on:
                        if pend.name == "prefill":
                            ttft_s = (t1 - t_wave0) / 1e9
                            self._m_ttft.observe(ttft_s)
                        else:
                            dt = (t1 - pend.t0_ns) / 1e9
                            decode_lat.append(dt)
                            self._m_decode_s.observe(dt)
                    pend = None
                if self._sharded:
                    # pad slots mirror their strip's last real group; the
                    # kept-slot table reads each request exactly once
                    vis = vis[self._shard["real_slots"]]
                for i, r in enumerate(requests):
                    if not done[i]:
                        tok = int(vis[i])
                        outs[i].append(tok)
                        if tok == r.eos_id or len(outs[i]) >= r.max_new_tokens:
                            done[i] = True
                if done.all() or pos >= self.max_len - 1:
                    break
                # the decode-step interval opens HERE so reshard stalls
                # (probe + cache permute) land inside the step's latency
                pend = tracer.begin("decode_step")
                cache, cur = self._maybe_reshard(requests, outs, cache, cur)
                if self._sharded:
                    frozen_dec = self._sharded_frozen_for(1)
                logits, cache = self._decode(
                    self.params, cur[:, None], cache, jnp.int32(pos),
                    frozen_dec
                )
                cache = self._keep_stats(cache, "decode", steps)
                if collect:
                    if self._sharded:
                        self._note_gm(self._shard["wmax_g"], self._ndev)
                    else:
                        self._note_gm(-(-b // tile))
                cur = jnp.argmax(logits, -1).astype(jnp.int32)
                pos += 1
            if pend is not None:
                # loop left by budget exhaustion with a step still in
                # flight: close its span at wall-clock now (no forced
                # block), but keep it out of the latency histogram — only
                # fully-blocked intervals are measurements
                pend.end()
            return self._close_wave(requests, outs, steps, deltas, ttft_s,
                                    decode_lat)

    def _counters(self) -> tuple:
        """The plan-cache, plan-store and re-shard counters at a wave's
        start, for the wave's deltas (`_spamm_stats`)."""
        cache = self.spamm_ctx.cache
        store = ((self.plan_store.hits, self.plan_store.misses)
                 if self.plan_store is not None else None)
        rs = self._resharder
        reshard = (rs.resharded, rs.probes) if rs is not None else None
        return cache.hits, cache.misses, store, reshard

    def _close_wave(self, requests, outs, steps, deltas, ttft_s,
                    decode_lat) -> List[np.ndarray]:
        """The wave's `wave_close`: one fetch of its gating stats, their
        aggregation and the registry feed, and each request's `out`.
        `deltas` holds the counters read at the wave's start (None
        without SpAMM)."""
        with self.obs.span("wave_close"):
            spamm_meta = (None if deltas is None else self._spamm_stats(
                steps, *deltas, ttft_s, decode_lat))
            results = [np.asarray(o, np.int32) for o in outs]
            if self.obs.enabled:
                self._m_waves.inc()
                self._m_tokens.inc(sum(len(o) for o in results))
            for r, toks_out in zip(requests, results):
                r.out = {"tokens": toks_out, "spamm": spamm_meta}
        return results

    def _keep_stats(self, cache, phase: str, steps: list):
        """A step's returned cache without its gating stats, which join the
        wave's `steps` (left on the device until the wave closes)."""
        if _STATS_KEY not in cache:
            return cache
        steps.append((phase, cache[_STATS_KEY]))
        return {k: v for k, v in cache.items() if k != _STATS_KEY}

    def _sharded_chunk_prefill(self, toks_in: np.ndarray, plen: int,
                               chunk: int, steps: list):
        """Prefill the padded sharded wave in `chunk`-token chunks at ONE
        static shard shape. Pad slots replicate live rows (the clamp-pad
        idiom), so every chunk runs the identical program; a partial final
        chunk clamp-pads its token tail and carries sentinel positions
        (>= max_len) there, whose drop-mode cache writes vanish. Returns
        (stacked full-length linear cache, final-chunk logits); each
        chunk's gating stats join `steps`."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        collect = self.spamm_ctx is not None and self.spamm_ctx.enable
        btot = toks_in.shape[0]
        per = btot // self._ndev
        one = self._pad_cache(
            M.init_cache(self.cfg, self.pcfg, per, self.max_len), full=True)
        stacked = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (self._ndev, *t.shape)), one)
        rows = NamedSharding(self._spamm_mesh, P("rows"))
        cache = jax.tree.map(lambda t: jax.device_put(t, rows), stacked)
        frozen_ck = self._sharded_frozen_for(chunk)
        logits = None
        for lo in range(0, plen, chunk):
            n = min(chunk, plen - lo)
            tk = np.empty((btot, chunk), np.int32)
            tk[:, :n] = toks_in[:, lo:lo + n]
            if n < chunk:
                tk[:, n:] = tk[:, n - 1:n]
            posr = np.full(chunk, self.max_len, np.int32)
            posr[:n] = lo + np.arange(n)
            pos = np.broadcast_to(posr, (btot, chunk)).copy()
            last = np.full(btot, n - 1 if lo + n >= plen else -1, np.int32)
            cache, logits = self._chunk(
                self.params, {"tokens": jnp.asarray(tk)}, cache,
                jnp.asarray(pos), jnp.asarray(last), frozen_ck)
            cache = self._keep_stats(cache, "prefill", steps)
            if collect:
                self._note_gm(self._shard["wmax_g"] * chunk, self._ndev)
            if self.obs.enabled:
                self._m_chunks.inc()
        return cache, logits

    def _generate_chunked(self, requests: List[Request],
                          chunk: int) -> List[np.ndarray]:
        """Slot scheduler: chunked prefill interleaved with decode over a
        power-of-two-bucketed slot pool. Per iteration: (1) queued requests
        are admitted into idle slots, (2) every prefilling slot advances by
        one `chunk`-token chunk at ONE static (slots, chunk) shape — a slot
        whose prompt ends inside the chunk captures its first generated
        token from that chunk's logits, (3) pending tokens are emitted and
        finished slots freed, (4) one decode step runs over the decoding
        slots at per-slot positions. Idle/pad lanes carry position
        sentinels (>= max_len): their cache writes drop and their outputs
        are never read. Termination per slot matches the lockstep wave
        exactly (EOS / max_new_tokens / pos >= max_len - 1 at emit time)."""
        b = len(requests)
        cap = min(b, self._max_slots) if self._max_slots else b
        nslots = _bucket(cap, 1)
        if self._max_slots and nslots > self._max_slots:
            # the bucket ladder rounds UP — past a non-power-of-two
            # max_slots that would run up to 2x the capped slot pool, so
            # floor to the largest power of two that honors the cap
            nslots = _floor_pow2(self._max_slots)
        # the wave's span opens here, as `_generate_wave`'s does
        with self.obs.span("wave", batch=b, slots=nslots, chunk=chunk):
            t_wave0 = time.perf_counter_ns()
            opening = self.obs.tracer.begin("wave_open")
            collect = self.spamm_ctx is not None and self.spamm_ctx.enable
            obs_on = self.obs.enabled
            tile = self.spamm_ctx.cfg.tile if collect else 0
            ttft_s = None
            decode_lat: list = []
            steps: list = []  # (phase, StepStats) of the wave, on the device
            deltas = self._counters() if collect else None
            frozen_ck = self._frozen_for(nslots * chunk)
            frozen_dec = self._frozen_for(nslots) if self._freeze else {}
            self._first = None
            cache = self._pad_cache(
                M.init_cache(self.cfg, self.pcfg, nslots, self.max_len),
                full=True)
            outs: List[list] = [[] for _ in range(b)]
            queue = list(range(b))
            slot_req = [-1] * nslots  # request index per slot, -1 when idle
            mode = ["idle"] * nslots  # idle | prefill | decode
            cursor = [0] * nslots     # prompt tokens already fed
            pos = [0] * nslots        # tokens materialized in the cache
            pending: List[Optional[int]] = [None] * nslots
            cur = np.zeros(nslots, np.int32)
            opening.end()
            while queue or any(m != "idle" for m in mode):
                if obs_on:
                    self._m_queue.observe(len(queue))
                # -- admission: queued requests claim idle slots --------------
                for s in range(nslots):
                    if mode[s] == "idle" and queue:
                        slot_req[s] = queue.pop(0)
                        mode[s] = "prefill"
                        cursor[s] = pos[s] = 0
                        pending[s] = None
                        if obs_on:
                            self._m_admit.inc()
                if obs_on:
                    self._m_occupancy.observe(sum(m != "idle" for m in mode))
                # -- one chunk of prefill over the prefilling slots -----------
                if any(m == "prefill" for m in mode):
                    tk = np.zeros((nslots, chunk), np.int32)
                    posc = np.full((nslots, chunk), self.max_len, np.int32)
                    last = np.full(nslots, -1, np.int32)
                    fin = []
                    for s in range(nslots):
                        if mode[s] != "prefill":
                            continue
                        pr = np.asarray(requests[slot_req[s]].prompt, np.int32)
                        n = min(len(pr) - cursor[s], chunk)
                        tk[s, :n] = pr[cursor[s]:cursor[s] + n]
                        if n < chunk:
                            tk[s, n:] = tk[s, n - 1]
                        posc[s, :n] = cursor[s] + np.arange(n)
                        cursor[s] += n
                        if cursor[s] >= len(pr):
                            last[s] = n - 1
                            fin.append(s)
                    with self.obs.span("prefill_chunk"):
                        cache, logits = self._chunk(
                            self.params, {"tokens": jnp.asarray(tk)}, cache,
                            jnp.asarray(posc), jnp.asarray(last), frozen_ck)
                        cache = self._keep_stats(cache, "prefill", steps)
                        step_tok = np.asarray(
                            jnp.argmax(logits, -1).astype(jnp.int32))
                    if obs_on:
                        self._m_chunks.inc()
                    if collect:
                        self._note_gm(-(-(nslots * chunk) // tile))
                    self._maybe_reshard(requests, outs)
                    for s in fin:
                        mode[s] = "decode"
                        pos[s] = len(requests[slot_req[s]].prompt)
                        pending[s] = int(step_tok[s])
                    if fin and ttft_s is None and obs_on:
                        ttft_s = (time.perf_counter_ns() - t_wave0) / 1e9
                        self._m_ttft.observe(ttft_s)
                # -- emit pending tokens; finished slots free -----------------
                for s in range(nslots):
                    if mode[s] != "decode" or pending[s] is None:
                        continue
                    r = requests[slot_req[s]]
                    tok = pending[s]
                    pending[s] = None
                    outs[slot_req[s]].append(tok)
                    if ((r.eos_id is not None and tok == r.eos_id)
                            or len(outs[slot_req[s]]) >= r.max_new_tokens
                            or pos[s] >= self.max_len - 1):
                        mode[s] = "idle"
                        slot_req[s] = -1
                # -- one decode step over the decoding slots ------------------
                dec = [s for s in range(nslots) if mode[s] == "decode"]
                if dec:
                    posv = np.full(nslots, self.max_len, np.int32)
                    for s in dec:
                        cur[s] = outs[slot_req[s]][-1]
                        posv[s] = pos[s]
                    step = self.obs.tracer.begin("decode_step")
                    logits, cache = self._decode(
                        self.params, jnp.asarray(cur)[:, None], cache,
                        jnp.asarray(posv), frozen_dec)
                    cache = self._keep_stats(cache, "decode", steps)
                    step_tok = np.asarray(
                        jnp.argmax(logits, -1).astype(jnp.int32))
                    t1 = step.end()
                    if obs_on:
                        dt = (t1 - step.t0_ns) / 1e9
                        decode_lat.append(dt)
                        self._m_decode_s.observe(dt)
                    if collect:
                        self._note_gm(-(-nslots // tile))
                    self._maybe_reshard(requests, outs)
                    for s in dec:
                        pending[s] = int(step_tok[s])
                        pos[s] += 1
            return self._close_wave(requests, outs, steps, deltas, ttft_s,
                                    decode_lat)
