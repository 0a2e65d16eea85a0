"""Decoder stack assembly: layer bodies, scan-over-layers, caches.

One generic stack covers all 10 archs:
  dense / vlm / audio : attention + MLP
  moe                 : attention + MoE block (shard_map inside the layer)
  ssm                 : Mamba2 block only
  hybrid              : 12 × (rec, rec, local-attn) groups + 2 rec tail,
                        each sub-layer followed by an MLP (Griffin residual
                        pattern: temporal-mix block and MLP block alternate)

Scan-over-layers keeps the HLO small (mandatory for the 512-chip dry-run);
per-layer FSDP all-gathers overlap with compute via the XLA scheduler.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.module import (SpammContext, StepStats, collect_taps,
                               maybe_spamm_matmul)
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_rope, embed, mlp, mlp_params, rms_norm


class NetCtx(NamedTuple):
    mesh: Mesh
    batch_axes: tuple = ("data",)
    model_axis: str = "model"

    def shard(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )


# ---------------------------------------------------------------------------
# attention layer
# ---------------------------------------------------------------------------

def attn_params(key, cfg: ModelConfig, dtype) -> dict:
    d, hq, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hk * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hk * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (hq * hd, d), dtype) / math.sqrt(hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hk * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hk * hd,), jnp.float32)
    return p


def _qkv(p, x, cfg: ModelConfig, ctx: NetCtx, positions, spamm_cfg=None,
         frozen=None, require_frozen: bool = False):
    b, s, d = x.shape
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cdt = x.dtype
    fz = frozen or {}
    q = maybe_spamm_matmul(x, p["wq"].astype(cdt), spamm_cfg,
                           frozen=fz.get("wq"), require_frozen=require_frozen,
                           site="wq")
    k = maybe_spamm_matmul(x, p["wk"].astype(cdt), spamm_cfg,
                           frozen=fz.get("wk"), require_frozen=require_frozen,
                           site="wk")
    v = maybe_spamm_matmul(x, p["wv"].astype(cdt), spamm_cfg,
                           frozen=fz.get("wv"), require_frozen=require_frozen,
                           site="wv")
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hk, hd)
    v = v.reshape(b, s, hk, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = ctx.shard(q, ctx.batch_axes, None, ctx.model_axis, None)
    return q, k, v


def attention_layer(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    spamm_cfg=None,
    return_kv: bool = False,
    frozen=None,
):
    q, k, v = _qkv(p, x, cfg, ctx, positions, spamm_cfg, frozen)
    o = attn_mod.flash_attention(
        q, k, v,
        causal=True,
        window=window,
        q_chunk=pcfg.attn_q_chunk,
        kv_chunk=pcfg.attn_kv_chunk,
    )
    o = o.reshape(*x.shape[:2], -1)
    out = maybe_spamm_matmul(o, p["wo"].astype(x.dtype), spamm_cfg,
                             frozen=(frozen or {}).get("wo"), site="wo")
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(
    p: dict,
    x: jax.Array,            # (B, 1, d)
    cache_k: jax.Array,      # (B, S, Hk, hd) — full or ring buffer
    cache_v: jax.Array,
    pos: jax.Array,          # scalar int32 (lockstep) or (B,) per-row index
                             # of the incoming token; per-row entries ≥ S are
                             # idle-slot sentinels — their cache writes DROP
                             # and their outputs are garbage the caller
                             # discards (chunked-engine slot scheduling)
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    *,
    window: Optional[int] = None,
    ring: bool = False,
    spamm_cfg=None,
    frozen=None,
):
    b = x.shape[0]
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pos = jnp.asarray(pos, jnp.int32)
    vector_pos = pos.ndim > 0
    posb = pos.reshape(b, 1) if vector_pos else jnp.full((b, 1), pos, jnp.int32)
    # decode gates only through frozen plans (require_frozen): re-tracing the
    # gate per decode step is never worth it, but a frozen weight side is
    q, k, v = _qkv(p, x, cfg, ctx, posb, spamm_cfg, frozen,
                   require_frozen=True)
    q1 = q[:, 0]  # (B, Hq, hd)
    if pcfg.decode_seq_shard and ctx.mesh is not None and ctx.mesh.shape[ctx.model_axis] > 1:
        if vector_pos:
            raise NotImplementedError(
                "decode_seq_shard expects a lockstep scalar position; "
                "per-row decode positions (chunked serving) need the "
                "unsharded decode path")
        o, cache_k, cache_v = attn_mod.decode_attention_seqsharded(
            q1, k, v, cache_k, cache_v, pos + 1,
            mesh=ctx.mesh, batch_axes=ctx.batch_axes, axis=ctx.model_axis,
            window=window, ring=ring,
        )
    elif vector_pos:
        # per-row scatter; mode="drop" discards rows whose position is out
        # of range, which is exactly the idle-slot sentinel contract. Per-row
        # positions REQUIRE a linear full-length cache (chunked serving pads
        # to max_len), so never apply the ring modulo here: for valid lanes
        # (pos < S) it is a no-op, while a sentinel (pos == max_len == S,
        # when sliding_window >= max_len keeps `ring` True) would wrap to
        # slot 0 and clobber a mid-prefill lane's K/V instead of dropping.
        slot = pos
        bi = jnp.arange(b)
        cache_k = cache_k.at[bi, slot].set(k[:, 0].astype(cache_k.dtype),
                                           mode="drop")
        cache_v = cache_v.at[bi, slot].set(v[:, 0].astype(cache_v.dtype),
                                           mode="drop")
        o = attn_mod.decode_attention(
            q1, cache_k, cache_v, pos + 1, window=window, ring=ring,
        )
    else:
        slot = (pos % cache_k.shape[1]) if ring else pos
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
        o = attn_mod.decode_attention(
            q1, cache_k, cache_v, pos + 1, window=window, ring=ring,
        )
    out = maybe_spamm_matmul(
        o.reshape(b, 1, hq * hd), p["wo"].astype(x.dtype), spamm_cfg,
        frozen=(frozen or {}).get("wo"), require_frozen=True, site="wo")
    return out, (cache_k, cache_v)


def attention_prefill_chunk(
    p: dict,
    x: jax.Array,            # (B, C, d) — one tile-aligned prompt chunk
    cache_k: jax.Array,      # (B, S, Hk, hd) — LINEAR cache (no ring)
    cache_v: jax.Array,
    positions: jax.Array,    # (B, C) int32 absolute positions; entries ≥ S
                             # are sentinels: the K/V write DROPS and the
                             # row's output is garbage the caller discards
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    *,
    window: Optional[int] = None,
    spamm_cfg=None,
    frozen=None,
):
    """One chunk of position-offset prefill: project/rope the chunk at its
    absolute positions, scatter K/V into the linear cache, then flash-attend
    the chunk's queries against the WHOLE cache with a per-row causal bias.

    Bit-parity contract with one-shot prefill (tile-aligned equal lengths):
    cache slots at/beyond each row's position are fully masked, and a fully
    masked KV block is bitwise neutral in the online softmax (NEG_INF
    absorbs finite f32 scores exactly; exp underflows to exact 0 and the
    rescale factor is exp(0)=1), so attending over max_len cache slots
    chunk by chunk reproduces the one-shot scores block for block."""
    b, c, _ = x.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, ctx, positions, spamm_cfg, frozen)
    bi = jnp.arange(b)[:, None]
    cache_k = cache_k.at[bi, positions].set(k.astype(cache_k.dtype),
                                            mode="drop")
    cache_v = cache_v.at[bi, positions].set(v.astype(cache_v.dtype),
                                            mode="drop")
    o = attn_mod.flash_attention(
        q, cache_k, cache_v,
        causal=True,
        window=window,
        q_chunk=pcfg.attn_q_chunk,
        kv_chunk=pcfg.attn_kv_chunk,
        q_offset=positions[:, 0],
    )
    out = maybe_spamm_matmul(
        o.reshape(b, c, hq * hd), p["wo"].astype(x.dtype), spamm_cfg,
        frozen=(frozen or {}).get("wo"), site="wo")
    return out, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def layer_params(key, cfg: ModelConfig, dtype, kind: str, model_axis_size: int):
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {
            "ln": jnp.zeros((cfg.d_model,), jnp.float32),
            "ssm": ssm_mod.ssm_params(ks[0], cfg.ssm, cfg.d_model, dtype),
        }
    p = {
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if kind == "rec":
        p["mix"] = rglru_mod.rglru_params(ks[0], cfg.rglru, cfg.d_model, dtype)
    else:
        p["mix"] = attn_params(ks[0], cfg, dtype)
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_params(ks[1], cfg.moe, cfg.d_model, dtype,
                                      model_axis_size)
    else:
        p["mlp"] = mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def _tap_ctx(spamm_cfg) -> Optional[SpammContext]:
    """The SpammContext whose taps a stack collects: None when there is
    nothing to collect (no gating, or a raw SpammConfig — maybe_spamm_matmul
    then builds throwaway contexts, so there is no shared buffer)."""
    if isinstance(spamm_cfg, SpammContext) and spamm_cfg.enable:
        return spamm_cfg
    return None


def _stack_stats(parts) -> Optional[StepStats]:
    """The step's `StepStats` from its layers' taps. Each part is `(values
    (num, n, 2), rows, per, base)`: the ys of a scan whose body ran `per`
    layers (or one unrolled layer, num 1), `rows` the body's static
    `(sub-layer, site, cost)` labels captured when it was traced, `base`
    the first layer's index."""
    if not parts or parts[0][0] is None:
        return None
    vals, layout = [], []
    for values, rows, per, base in parts:
        vals.append(values.reshape(-1, 2))
        layout += [(base + it * per + i, site, cost)
                   for it in range(values.shape[0])
                   for i, site, cost in rows]
    return StepStats(jnp.concatenate(vals), tuple(layout))


def _ffn(p, h, cfg: ModelConfig, ctx: NetCtx, spamm_cfg, frozen=None,
         require_frozen: bool = False):
    """MLP or MoE sub-layer on normalized input h. Returns (out, aux).

    MoE blocks keep the traced gating path (their expert buffers live
    inside shard_map; frozen plans cover the dense attention/MLP GEMMs)."""
    if cfg.moe is not None:
        return moe_mod.moe_block(
            p["moe"], h, cfg.moe, cfg.act,
            mesh=ctx.mesh, batch_axes=ctx.batch_axes,
            model_axis=ctx.model_axis,
            spamm_cfg=None if require_frozen else spamm_cfg,
        )
    return mlp(p["mlp"], h, cfg.act, spamm_cfg, frozen,
               require_frozen), jnp.float32(0.0)


def layer_fwd(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    positions: jax.Array,
    kind: str,                  # "attn" | "rec" | "ssm"
    *,
    spamm_cfg=None,
    collect_cache: bool = False,
    frozen=None,
):
    """One residual layer. Returns (x, aux, cache). `frozen` is this
    layer's {"mix": {...}, "mlp": {...}} dict of FrozenPlan jit inputs."""
    fz = frozen or {}
    if pcfg.seq_shard_acts and x.shape[1] > 1:
        # Megatron-SP: residual stream seq-sharded over the model axis; GSPMD
        # turns the TP psum into reduce-scatter + all-gather (half the wire
        # bytes) and shards norms/elementwise over seq.
        x = ctx.shard(x, ctx.batch_axes, ctx.model_axis, None)
    else:
        x = ctx.shard(x, ctx.batch_axes, None, None)
    if kind == "ssm":
        h, cache = ssm_mod.ssm_block(p["ssm"], rms_norm(x, p["ln"], cfg.norm_eps),
                                     cfg.ssm, norm_eps=cfg.norm_eps)
        return x + h, jnp.float32(0.0), (cache if collect_cache else None)

    window = cfg.sliding_window if kind == "attn" else None
    if kind == "attn":
        if collect_cache:
            h, (k, v) = attention_layer(
                p["mix"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, pcfg, ctx,
                positions, window=window, spamm_cfg=spamm_cfg, return_kv=True,
                frozen=fz.get("mix"),
            )
            cache = {"k": k, "v": v}
        else:
            h = attention_layer(
                p["mix"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, pcfg, ctx,
                positions, window=window, spamm_cfg=spamm_cfg,
                frozen=fz.get("mix"),
            )
            cache = None
    else:  # rec
        h, cache = rglru_mod.rglru_block(
            p["mix"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg.rglru
        )
        cache = cache if collect_cache else None
    x = x + h
    f, aux = _ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps), cfg, ctx, spamm_cfg,
                  fz.get("mlp"))
    return x + f, aux, cache


def layer_prefill_chunk(
    p: dict,
    x: jax.Array,               # (B, C, d)
    cache: dict,
    positions: jax.Array,       # (B, C) absolute positions (sentinels ≥ S)
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    *,
    spamm_cfg=None,
    frozen=None,
):
    """One residual layer of chunked prefill: attention writes the chunk's
    K/V into the linear cache at its absolute positions; the FFN is
    stateless per position, so it is the plain prefill body. Only "attn"
    stacks chunk — recurrent state (ssm/rec) would have to thread through
    every chunk carry, which is the decode path's job."""
    fz = frozen or {}
    x = ctx.shard(x, ctx.batch_axes, None, None)
    h, (ck, cv) = attention_prefill_chunk(
        p["mix"], rms_norm(x, p["ln1"], cfg.norm_eps), cache["k"], cache["v"],
        positions, cfg, pcfg, ctx, window=cfg.sliding_window,
        spamm_cfg=spamm_cfg, frozen=fz.get("mix"),
    )
    new = dict(cache, k=ck, v=cv)
    x = x + h
    f, _ = _ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps), cfg, ctx, spamm_cfg,
                fz.get("mlp"))
    return x + f, new


def layer_decode(
    p: dict,
    x: jax.Array,               # (B, 1, d)
    cache: dict,
    pos: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    kind: str,
    *,
    spamm_cfg=None,
    frozen=None,
):
    fz = frozen or {}
    if kind == "ssm":
        h, new = ssm_mod.ssm_decode_step(
            p["ssm"], rms_norm(x[:, 0], p["ln"], cfg.norm_eps), cache, cfg.ssm,
            norm_eps=cfg.norm_eps,
        )
        return x + h[:, None], new

    if kind == "attn":
        # ring buffer iff the cache is exactly the sliding window (static)
        ring = (
            cfg.sliding_window is not None
            and cache["k"].shape[1] <= cfg.sliding_window
        )
        h, (ck, cv) = attention_decode(
            p["mix"], rms_norm(x, p["ln1"], cfg.norm_eps),
            cache["k"], cache["v"], pos, cfg, pcfg, ctx,
            window=cfg.sliding_window, ring=ring,
            spamm_cfg=spamm_cfg, frozen=fz.get("mix"),
        )
        new = dict(cache, k=ck, v=cv)
    else:
        h1, new = rglru_mod.rglru_decode_step(
            p["mix"], rms_norm(x[:, 0], p["ln1"], cfg.norm_eps), cache, cfg.rglru
        )
        h = h1[:, None]
    x = x + h
    f, _ = _ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps), cfg, ctx, spamm_cfg,
                fz.get("mlp"), require_frozen=True)
    return x + f, new


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def hybrid_pattern(cfg: ModelConfig):
    """(n_groups, group_kinds, tail_kinds) for the hybrid arch."""
    pat = cfg.rglru.block_pattern  # ("rec", "rec", "attn")
    kinds = {"rec": "rec", "attn": "attn"}
    glen = len(pat)
    n_groups = cfg.num_layers // glen
    tail = cfg.num_layers - n_groups * glen
    return n_groups, tuple(kinds[k] for k in pat), ("rec",) * tail


def stack_kinds(cfg: ModelConfig):
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "hybrid":
        return "hybrid"
    return "attn"


def _remat(fn, pcfg: ParallelConfig):
    if pcfg.remat == "none":
        return fn
    if pcfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def stack_fwd(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    positions: jax.Array,
    *,
    spamm_cfg=None,
    collect_spamm_stats: bool = False,
):
    """Run all layers (train/loss path, no caches). Returns (x, aux), or
    (x, aux, (frac_sum, gemm_count, layer_frac_sums, layer_gemm_counts))
    with `collect_spamm_stats` — the last two are (num_layers,) f32 arrays
    of per-layer fraction sums / gated-GEMM counts.

    The stats ride the scan carry/ys as traced values (the context's trace
    buffer, opened per layer), which survive grad-of-custom_vjp and remat,
    so the train step exports the same per-GEMM fractions the serving
    engine's steps return."""
    kind = stack_kinds(cfg)
    tctx = _tap_ctx(spamm_cfg) if collect_spamm_stats else None

    def tapped_layer(p, h, k):
        """layer_fwd with its gated-GEMM taps captured as traced values."""
        (h, a, _), tv, _ = collect_taps(tctx, layer_fwd, p, h, cfg, pcfg,
                                        ctx, positions, k,
                                        spamm_cfg=spamm_cfg)
        if tv is None:
            return h, a, jnp.float32(0.0), jnp.float32(0.0)
        return h, a, jnp.sum(tv[:, 0]), jnp.float32(tv.shape[0])

    zero = jnp.float32(0.0)

    if kind == "hybrid":
        n_groups, gkinds, tail = hybrid_pattern(cfg)

        def gbody(carry, p):
            h, aux, vs, vc = carry
            ss, cs = [], []
            for i, k in enumerate(gkinds):
                h, a, s, c = tapped_layer(p[f"l{i}"], h, k)
                aux, vs, vc = aux + a, vs + s, vc + c
                ss.append(s)
                cs.append(c)
            return (h, aux, vs, vc), (jnp.stack(ss), jnp.stack(cs))

        (x, aux, vs, vc), (gss, gcs) = jax.lax.scan(
            _remat(gbody, pcfg), (x, zero, zero, zero), params["groups"]
        )
        # per-layer ys come out (n_groups, glen); flatten to stack order and
        # append the unrolled tail
        lvs = [gss.reshape(-1)]
        lvc = [gcs.reshape(-1)]
        for i, k in enumerate(tail):
            x, a, s, c = tapped_layer(params["tail"][f"l{i}"], x, k)
            aux, vs, vc = aux + a, vs + s, vc + c
            lvs.append(s[None])
            lvc.append(c[None])
        if tctx is not None:
            return x, aux, (vs, vc, jnp.concatenate(lvs),
                            jnp.concatenate(lvc))
        return x, aux

    def body(carry, p):
        h, aux, vs, vc = carry
        h, a, s, c = tapped_layer(p, h, kind)
        return (h, aux + a, vs + s, vc + c), (s, c)

    if pcfg.scan_layers:
        (x, aux, vs, vc), (lvs, lvc) = jax.lax.scan(
            _remat(body, pcfg), (x, zero, zero, zero), params["layers"]
        )
    else:
        aux = vs = vc = zero
        ls, lc = [], []
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda t: t[i], params["layers"])
            (x, aux, vs, vc), (s, c) = _remat(body, pcfg)((x, aux, vs, vc), p)
            ls.append(s)
            lc.append(c)
        lvs, lvc = jnp.stack(ls), jnp.stack(lc)
    return (x, aux, (vs, vc, lvs, lvc)) if tctx is not None else (x, aux)


def stack_prefill(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    positions: jax.Array,
    cache_len: int,
    *,
    spamm_cfg=None,
    frozen=None,
):
    """Forward + collect caches. Returns (x, cache_pytree, stats).

    `spamm_cfg` is the SpammContext the serving engine threads so prefill
    GEMMs run through the plan/execute pipeline like the train forward.
    `frozen` mirrors the params structure at the gated-weight subtrees with
    FrozenPlan jit inputs (stacked per layer under "layers"/"groups" — they
    ride the layer scan as a second xs); {} / missing keys fall back to the
    traced gate.

    `stats` is the step's `StepStats` (None without an enabled
    SpammContext): each layer's gated-GEMM taps ride the scan as ys and are
    labeled by layer and site."""
    kind = stack_kinds(cfg)
    s = x.shape[1]
    fz = frozen or {}
    tctx = _tap_ctx(spamm_cfg)

    def trim(c):
        """Ring-ify sliding-window KV caches: token t lives at slot t % W."""
        if c is None:
            return None
        if "k" in c and c["k"].shape[1] > cache_len:
            w = cache_len
            tail_k, tail_v = c["k"][:, -w:], c["v"][:, -w:]
            shift = s % w  # tail index i holds token (s - w + i) → slot (s+i)%w
            if shift:
                tail_k = jnp.roll(tail_k, shift, axis=1)
                tail_v = jnp.roll(tail_v, shift, axis=1)
            c = dict(c, k=tail_k, v=tail_v)
        return c

    def layer(p, h, k, f):
        (h, _, c), tv, rows = collect_taps(
            tctx, layer_fwd, p, h, cfg, pcfg, ctx, positions, k,
            spamm_cfg=spamm_cfg, collect_cache=True, frozen=f)
        return h, trim(c), tv, rows

    if kind == "hybrid":
        n_groups, gkinds, tail = hybrid_pattern(cfg)
        glen = len(gkinds)
        grows = []

        def gbody(h, pf):
            p, f = pf
            caches, tvs, rows = {}, [], []
            for i, k in enumerate(gkinds):
                h, caches[f"l{i}"], tv, r = layer(p[f"l{i}"], h, k,
                                                  f.get(f"l{i}"))
                tvs.append(tv)
                rows += [(i,) + y for y in r]
            grows[:] = rows
            return h, (caches, None if tctx is None else
                       jnp.concatenate(tvs))

        x, (gcaches, gtv) = jax.lax.scan(
            gbody, x, (params["groups"], fz.get("groups", {})))
        parts = [(gtv, grows, glen, 0)]
        tcaches = {}
        for i, k in enumerate(tail):
            x, tcaches[f"l{i}"], tv, r = layer(
                params["tail"][f"l{i}"], x, k,
                fz.get("tail", {}).get(f"l{i}"))
            parts.append((None if tv is None else tv[None],
                          [(0,) + y for y in r], 1, n_groups * glen + i))
        return (x, {"groups": gcaches, "tail": tcaches},
                _stack_stats(parts))

    rows = []

    def body(h, pf):
        p, f = pf
        h, c, tv, r = layer(p, h, kind, f)
        rows[:] = [(0,) + y for y in r]
        return h, (c, tv)

    x, (caches, tv) = jax.lax.scan(body, x, (params["layers"],
                                             fz.get("layers", {})))
    return x, {"layers": caches}, _stack_stats([(tv, rows, 1, 0)])


def stack_prefill_chunk(
    params: dict,
    x: jax.Array,          # (B, C, d) — one chunk of embedded prompt tokens
    cache: dict,
    positions: jax.Array,  # (B, C) absolute positions (sentinels ≥ max_len)
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    *,
    spamm_cfg=None,
    frozen=None,
):
    """Chunked prefill over the layer stack: like `stack_decode`, the decode
    caches ride the scan as xs and come back as ys, so each chunk runs at
    ONE static (B, C) shape regardless of where in the prompt it lands.
    Attention ("attn") stacks only — ssm/hybrid recurrent state cannot
    resume from a position offset without threading the whole state chain.

    Returns (x, cache, stats), the stats as `stack_prefill`'s."""
    kind = stack_kinds(cfg)
    if kind != "attn":
        raise NotImplementedError(
            f"chunked prefill covers stateless-FFN attention stacks only "
            f"(got stack kind {kind!r}: recurrent prefill state does not "
            f"checkpoint at a chunk boundary)")
    fz = frozen or {}
    tctx = _tap_ctx(spamm_cfg)
    rows = []

    def body(h, pcf):
        p, c, f = pcf
        (h, nc), tv, r = collect_taps(tctx, layer_prefill_chunk, p, h, c,
                                      positions, cfg, pcfg, ctx,
                                      spamm_cfg=spamm_cfg, frozen=f)
        rows[:] = [(0,) + y for y in r]
        return h, (nc, tv)

    x, (caches, tv) = jax.lax.scan(body, x, (params["layers"],
                                             cache["layers"],
                                             fz.get("layers", {})))
    return x, {"layers": caches}, _stack_stats([(tv, rows, 1, 0)])


def stack_decode(
    params: dict,
    x: jax.Array,          # (B, 1, d)
    cache: dict,
    pos: jax.Array,
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    ctx: NetCtx,
    *,
    spamm_cfg=None,
    frozen=None,
):
    """Decode gating is frozen-plan-only: sites with a FrozenPlan run the
    compiled work-list, sites without fall back to dense (require_frozen in
    `layer_decode`) — per-step re-tracing of the gate is never paid.

    Returns (x, cache, stats), the stats as `stack_prefill`'s."""
    kind = stack_kinds(cfg)
    fz = frozen or {}
    tctx = _tap_ctx(spamm_cfg)

    def layer(p, h, c, k, f):
        return collect_taps(tctx, layer_decode, p, h, c, pos, cfg, pcfg,
                            ctx, k, spamm_cfg=spamm_cfg, frozen=f)

    if kind == "hybrid":
        n_groups, gkinds, tail = hybrid_pattern(cfg)
        glen = len(gkinds)
        grows = []

        def gbody(h, pcf):
            p, c, f = pcf
            newc, tvs, rows = {}, [], []
            for i, k in enumerate(gkinds):
                (h, newc[f"l{i}"]), tv, r = layer(p[f"l{i}"], h, c[f"l{i}"],
                                                  k, f.get(f"l{i}"))
                tvs.append(tv)
                rows += [(i,) + y for y in r]
            grows[:] = rows
            return h, (newc, None if tctx is None else jnp.concatenate(tvs))

        x, (gcaches, gtv) = jax.lax.scan(
            gbody, x, (params["groups"], cache["groups"],
                       fz.get("groups", {})))
        parts = [(gtv, grows, glen, 0)]
        tcaches = {}
        for i, k in enumerate(tail):
            (x, tcaches[f"l{i}"]), tv, r = layer(
                params["tail"][f"l{i}"], x, cache["tail"][f"l{i}"], k,
                fz.get("tail", {}).get(f"l{i}"))
            parts.append((None if tv is None else tv[None],
                          [(0,) + y for y in r], 1, n_groups * glen + i))
        return (x, {"groups": gcaches, "tail": tcaches},
                _stack_stats(parts))

    rows = []

    def body(h, pcf):
        p, c, f = pcf
        (h, nc), tv, r = layer(p, h, c, kind, f)
        rows[:] = [(0,) + y for y in r]
        return h, (nc, tv)

    x, (caches, tv) = jax.lax.scan(body, x, (params["layers"],
                                             cache["layers"],
                                             fz.get("layers", {})))
    return x, {"layers": caches}, _stack_stats([(tv, rows, 1, 0)])
