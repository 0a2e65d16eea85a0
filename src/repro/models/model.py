"""Top-level model API: init, sharding specs, train/prefill/decode steps.

* `init_params(cfg, pcfg, key)` — full parameter pytree (use under
  jax.eval_shape for the dry-run: no allocation).
* `param_pspecs(cfg, pcfg, params)` — PartitionSpec pytree implementing the
  DP(+pod)/FSDP/TP/EP rules of DESIGN.md §5.
* `loss_fn / make_train_step / make_prefill_step / make_decode_step` — the
  jit-able step functions the launcher lowers.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.core import module as spmod
from repro.models import transformer as tr
from repro.models.layers import chunked_ce_loss, rms_norm
from repro.models import ssm as ssm_mod
from repro.models.transformer import NetCtx


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, pcfg: ParallelConfig, key,
                model_axis_size: int = 1) -> dict:
    pdt = _dtype(pcfg.param_dtype)
    k_emb, k_layers, k_un = jax.random.split(key, 3)
    params: dict = {
        "embed": {
            "embedding": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), pdt)
            * (1.0 / math.sqrt(cfg.d_model))
        },
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        "unembed": {
            "kernel": jax.random.normal(k_un, (cfg.d_model, cfg.vocab), pdt)
            * (1.0 / math.sqrt(cfg.d_model))
        },
    }
    kind = tr.stack_kinds(cfg)
    if kind == "hybrid":
        n_groups, gkinds, tail = tr.hybrid_pattern(cfg)
        gkeys = jax.random.split(k_layers, n_groups + len(tail))

        def one_group(k):
            ks = jax.random.split(k, len(gkinds))
            return {
                f"l{i}": tr.layer_params(ks[i], cfg, pdt, gk, model_axis_size)
                for i, gk in enumerate(gkinds)
            }

        params["groups"] = jax.vmap(one_group)(gkeys[:n_groups])
        params["tail"] = {
            f"l{i}": tr.layer_params(gkeys[n_groups + i], cfg, pdt, tk,
                                     model_axis_size)
            for i, tk in enumerate(tail)
        }
    else:
        lkeys = jax.random.split(k_layers, cfg.num_layers)
        fd = cfg.first_dense
        if fd:
            params["dense"] = jax.vmap(
                lambda k: tr.layer_params(k, cfg, pdt, kind, model_axis_size,
                                          dense=True)
            )(lkeys[:fd])
        params["layers"] = jax.vmap(
            lambda k: tr.layer_params(k, cfg, pdt, kind, model_axis_size)
        )(lkeys[fd:])
    return params


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def _leaf_spec(path: str, shape, cfg: ModelConfig, pcfg: ParallelConfig,
               stacked: bool) -> P:
    """PartitionSpec for one parameter leaf; `stacked` = has leading L dim."""
    fsdp = "data" if pcfg.fsdp else None
    m = "model"
    rules = []
    if "embedding" in path:
        rules = [m, fsdp]
    elif "unembed" in path:
        rules = [fsdp, m]
    elif "moe" in path:
        ep = cfg.moe is not None and cfg.moe.impl == "ep"
        if "router" in path or "gate" in path:
            rules = [None] * len(shape)
        elif "shared" in path:
            rules = [fsdp, m] if path.endswith("w1") or path.endswith("w3") else [m, fsdp]
        elif ep:
            rules = [m, None, None]           # experts over model, replicated DP
        elif path.endswith("w2"):
            rules = [None, m, fsdp]           # (E, ff, d)
        else:
            rules = [None, fsdp, m]           # (E, d, ff)
    elif any(k in path for k in ("wq", "wk", "wv", "in_proj", "in_gelu",
                                 "in_rec", "w1", "w3")):
        rules = [fsdp, m]
    elif any(k in path for k in ("wo", "out_proj", "w2")) or path.endswith("out"):
        rules = [m, fsdp]
    elif path.endswith("conv") or "conv" in path.split("/")[-1]:
        rules = [None, m] if len(shape) >= 2 else [None]
    else:
        rules = [None] * len(shape)
    base = len(shape) - len(rules)
    if base < 0:  # rank-1 leaf (biases) matched a 2-D rule
        rules = [None] * len(shape)
        base = 0
    return P(*([None] * base + rules))


def param_pspecs(cfg: ModelConfig, pcfg: ParallelConfig, params) -> Any:
    def walk(tree, prefix, stacked):
        if isinstance(tree, dict):
            return {
                k: walk(v, f"{prefix}/{k}",
                        stacked or k in ("layers", "groups"))
                for k, v in tree.items()
            }
        return _leaf_spec(prefix, tree.shape, cfg, pcfg, stacked)

    return walk(params, "", False)


def shardings_for(mesh: Mesh, tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def forward_hidden(cfg, pcfg, ctx: NetCtx, params, batch, *, spamm_cfg=None,
                   collect_spamm_stats: bool = False):
    """tokens or embeds → final-normed hidden states (B, S, d).

    `spamm_cfg` may be a SpammConfig or a prebuilt `SpammContext` (config +
    shared WeightPlanCache); the stack threads the context object, not raw
    (tau, tile, backend, block_n) tuples. With `collect_spamm_stats` the
    return gains a third element (frac_sum, gemm_count, layer_frac_sums,
    layer_gemm_counts) of traced gating-stat values (see `stack_fwd`)."""
    spamm_cfg = spmod.as_context(spamm_cfg)
    cdt = _dtype(pcfg.compute_dtype)
    if "embeds" in batch:
        x = batch["embeds"].astype(cdt)
    else:
        x = params["embed"]["embedding"].astype(cdt)[batch["tokens"]]
    x = ctx.shard(x, ctx.batch_axes, None, None)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    out = tr.stack_fwd(params, x, cfg, pcfg, ctx, positions,
                       spamm_cfg=spamm_cfg,
                       collect_spamm_stats=collect_spamm_stats)
    if len(out) == 3:
        x, aux, spamm_stats = out
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux, spamm_stats
    x, aux = out
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def loss_fn(cfg, pcfg, ctx, params, batch, *, spamm_cfg=None):
    spamm_cfg = spmod.as_context(spamm_cfg)
    collect = spamm_cfg is not None and spamm_cfg.enable
    if collect:
        h, aux, (vs, vc, lvs, lvc) = forward_hidden(
            cfg, pcfg, ctx, params, batch, spamm_cfg=spamm_cfg,
            collect_spamm_stats=True)
    else:
        h, aux = forward_hidden(cfg, pcfg, ctx, params, batch,
                                spamm_cfg=spamm_cfg)
    unembed = params["unembed"]["kernel"].astype(h.dtype)
    ce = chunked_ce_loss(h, unembed, batch["labels"], pcfg.loss_chunk)
    aux_w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0
    met = {"ce": ce, "aux": aux}
    if collect:
        # same per-GEMM gating stats the serving engine taps, exported as
        # step metrics (mean valid fraction over the step's gated GEMMs),
        # plus the per-layer breakdown (stack order, (num_layers,) arrays)
        met["spamm_valid_fraction"] = vs / jnp.maximum(vc, 1.0)
        met["spamm_gated_gemms"] = vc
        met["spamm_layer_valid_fraction"] = lvs / jnp.maximum(lvc, 1.0)
        met["spamm_layer_gated_gemms"] = lvc
    return ce + aux_w * aux, met


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, pcfg: ParallelConfig, batch: int,
               max_len: int) -> dict:
    """Zeroed decode caches (use under eval_shape for specs)."""
    cdt = _dtype(pcfg.compute_dtype)
    kind = tr.stack_kinds(cfg)
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def attn_cache():
        s = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        if cfg.mla is not None:      # latents and roped keys, no per-head K/V
            return {"lat": jnp.zeros((batch, s, cfg.mla.latent_dim), cdt)}
        return {
            "k": jnp.zeros((batch, s, hk, hd), cdt),
            "v": jnp.zeros((batch, s, hk, hd), cdt),
        }

    def ssm_cache():
        dims = ssm_mod.ssm_dims(cfg.ssm, cfg.d_model)
        return {
            "state": jnp.zeros((batch, dims.heads, cfg.ssm.head_dim,
                                cfg.ssm.state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm.conv_dim - 1, dims.conv_ch), cdt),
        }

    def rec_cache():
        w = cfg.rglru.lru_width or cfg.d_model
        return {
            "h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.rglru.conv_dim - 1, w), cdt),
        }

    def stack_cache(mk, n):
        one = mk()
        return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n, *t.shape)), one)

    if kind == "ssm":
        return {"layers": stack_cache(ssm_cache, cfg.num_layers)}
    if kind == "hybrid":
        n_groups, gkinds, tail = tr.hybrid_pattern(cfg)
        group = {
            f"l{i}": (rec_cache() if k == "rec" else attn_cache())
            for i, k in enumerate(gkinds)
        }
        groups = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_groups, *t.shape)), group
        )
        tailc = {f"l{i}": rec_cache() for i, _ in enumerate(tail)}
        return {"groups": groups, "tail": tailc}
    if cfg.first_dense:
        return {"dense": stack_cache(attn_cache, cfg.first_dense),
                "layers": stack_cache(attn_cache,
                                      cfg.num_layers - cfg.first_dense)}
    return {"layers": stack_cache(attn_cache, cfg.num_layers)}


def cache_pspecs(cfg: ModelConfig, pcfg: ParallelConfig, cache,
                 batch_axes=("data",), model_axis="model",
                 batch_replicated: bool = False) -> Any:
    """Sequence-sharded attention caches; states sharded over model width."""
    ba = None if batch_replicated else batch_axes

    def leaf(path, t):
        if path.endswith("/k") or path.endswith("/v"):
            # (L, B, S, Hk, hd) or (B, S, Hk, hd)
            lead = [None] * (t.ndim - 4)
            return P(*lead, ba, model_axis, None, None)
        if path.endswith("state"):        # (L, B, H, P, N)
            lead = [None] * (t.ndim - 4)
            return P(*lead, ba, model_axis, None, None)
        if path.endswith("/h"):           # (L, B, W)
            lead = [None] * (t.ndim - 2)
            return P(*lead, ba, model_axis)
        if path.endswith("conv"):         # (L, B, K-1, ch)
            lead = [None] * (t.ndim - 3)
            return P(*lead, ba, None, model_axis)
        return P(*([None] * t.ndim))

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}") for k, v in tree.items()}
        return leaf(prefix, tree)

    return walk(cache, "")


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, pcfg: ParallelConfig, ctx: NetCtx,
                    optimizer, *, spamm_cfg=None):
    """Returns fn(params, opt_state, batch, step) → (params, opt_state, metrics)."""
    spamm_cfg = spmod.as_context(spamm_cfg)  # one context for every call

    def step(params, opt_state, batch, step_no):
        (loss, met), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, pcfg, ctx, p, batch, spamm_cfg=spamm_cfg),
            has_aux=True,
        )(params)
        params, opt_state, gnorm = optimizer.update(params, grads, opt_state,
                                                    step_no)
        metrics = {"loss": loss, "grad_norm": gnorm, **met}
        return params, opt_state, metrics

    return step


def make_prefill_step(cfg: ModelConfig, pcfg: ParallelConfig, ctx: NetCtx,
                      *, spamm_cfg=None):
    """fn(params, batch, frozen=None) → (cache, last_logits, stats). Logits
    only for the final position (materializing (B, S, V) at 32k is not a
    production thing).

    `frozen` is the optional pytree of precomputed weight-side SpAMM plans
    (see `repro.plans`): a jit ARGUMENT, so the compiled graph consumes the
    step tables as data instead of re-deriving weight normmaps per trace.

    `stats` is the step's gating stats (a `module.StepStats`), None
    without an enabled SpammContext."""
    spamm_cfg = spmod.as_context(spamm_cfg)  # one context for every call

    def step(params, batch, frozen=None):
        cdt = _dtype(pcfg.compute_dtype)
        if "embeds" in batch:
            x = batch["embeds"].astype(cdt)
        else:
            x = params["embed"]["embedding"].astype(cdt)[batch["tokens"]]
        x = ctx.shard(x, ctx.batch_axes, None, None)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        cache_len = (min(cfg.sliding_window, s) if cfg.sliding_window else s)
        x, cache, stats = tr.stack_prefill(params, x, cfg, pcfg, ctx,
                                           positions, cache_len,
                                           spamm_cfg=spamm_cfg, frozen=frozen)
        h_last = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
        logits = (h_last @ params["unembed"]["kernel"].astype(cdt)).astype(jnp.float32)
        return cache, logits, stats

    return step


def make_prefill_chunk_step(cfg: ModelConfig, pcfg: ParallelConfig,
                            ctx: NetCtx, *, spamm_cfg=None):
    """fn(params, batch, cache, positions, last_idx, frozen=None) →
    (cache, logits, stats). One tile-aligned chunk of position-offset
    prefill: the chunk's tokens run the layer stack at ONE static (B, C)
    shape, writing K/V into the LINEAR decode cache at `positions` (B, C) —
    absolute per-row token indices; entries ≥ cache length are idle/pad
    sentinels whose writes drop (`.at[].set(mode="drop")`). `logits` (B, V) is read
    at `last_idx` (B,), the in-chunk index of each row's final prompt token
    (clamped, so rows whose prompt does not end in this chunk return values
    the caller ignores). Attention stacks only — see `stack_prefill_chunk`.

    `frozen` is the chunk-shape FrozenPlan pytree (rows = B·C), a jit
    argument exactly like the one-shot prefill's; `stats` as the one-shot
    prefill's."""
    spamm_cfg = spmod.as_context(spamm_cfg)  # one context for every call

    def step(params, batch, cache, positions, last_idx, frozen=None):
        cdt = _dtype(pcfg.compute_dtype)
        if "embeds" in batch:
            x = batch["embeds"].astype(cdt)
        else:
            x = params["embed"]["embedding"].astype(cdt)[batch["tokens"]]
        x = ctx.shard(x, ctx.batch_axes, None, None)
        b, c, _ = x.shape
        x, cache, stats = tr.stack_prefill_chunk(
            params, x, cache, positions, cfg, pcfg, ctx,
            spamm_cfg=spamm_cfg, frozen=frozen)
        idx = jnp.clip(last_idx, 0, c - 1)
        h_last = rms_norm(x[jnp.arange(b), idx], params["final_norm"],
                          cfg.norm_eps)
        logits = (h_last @ params["unembed"]["kernel"].astype(cdt)).astype(jnp.float32)
        return cache, logits, stats

    return step


def make_decode_step(cfg: ModelConfig, pcfg: ParallelConfig, ctx: NetCtx,
                     *, spamm_cfg=None):
    """fn(params, tokens_or_embeds (B,1[,d]), cache, pos, frozen=None) →
    (logits, cache, stats). Decode GEMMs gate only through `frozen` plans
    (sites without one stay dense — see `stack_decode`); `stats` as the
    prefill's."""
    spamm_cfg = spmod.as_context(spamm_cfg)  # one context for every call

    def step(params, inp, cache, pos, frozen=None):
        cdt = _dtype(pcfg.compute_dtype)
        if inp.ndim == 3:
            x = inp.astype(cdt)
        else:
            x = params["embed"]["embedding"].astype(cdt)[inp]
        x = ctx.shard(x, ctx.batch_axes, None, None)
        x, cache, stats = tr.stack_decode(params, x, cache, pos, cfg, pcfg,
                                          ctx, spamm_cfg=spamm_cfg,
                                          frozen=frozen)
        h = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
        logits = (h @ params["unembed"]["kernel"].astype(cdt)).astype(jnp.float32)
        return logits, cache, stats

    return step


def reshard_probe(controller, spamm_ctx, params, step: int, *,
                  tokens=None, x=None) -> None:
    """Shared body of the drift-triggered re-sharding probe (serving engine
    and train loop both call this — one implementation, one drift behavior).

    Activation rows come from `x` directly (frontend archs feed embeds) or
    from embedding `tokens` through the model's table (ids clamped into the
    vocab). Their norms are computed FRESH; the weight side piggybacks on
    the cached `WeightPlanCache.weight_side` norms of the unembed kernel —
    present for every arch and shaped like every gated GEMM's weight side —
    so a probe costs one activation get-norm, nothing else. Feeds the
    controller only when the row grid has at least one row per device."""
    scfg = spamm_ctx.cfg
    lv = controller.cfg.level
    if x is None:
        emb = params["embed"]["embedding"]
        ids = jnp.asarray(np.asarray(tokens, np.int64) % emb.shape[0])
        x = jnp.take(jnp.asarray(emb), ids, axis=0)
    from repro.core import schedule as _schedule  # circular-safe

    _, nw = spamm_ctx.cache.weight_side(
        params["unembed"]["kernel"], tile=scfg.tile, backend=scfg.backend,
        levels=lv)
    v, fine_rows = _schedule.probe_v_estimate(
        x, nw, scfg.tau, tile=scfg.tile, backend=scfg.backend, level=lv)
    if fine_rows >= controller.cfg.num_devices:
        controller.probe(v, step, level=lv, fine_rows=fine_rows)
