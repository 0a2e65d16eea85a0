"""Mixture-of-Experts with two production sharding strategies (DESIGN.md §5).

Both run inside shard_map (token dispatch must stay local to a data shard —
a pjit-level sort would become a global collective):

* impl="tp"  (mixtral-8x22b): every chip holds ALL experts, ff-dim sharded
  over `model`; local sort-based dispatch → grouped GEMM → psum(model) for
  the down-projection. No token movement at all.
* impl="ep"  (qwen2-moe): experts sharded over `model` (padded to a multiple
  of the axis size); tokens replicated over `model`, each chip computes only
  its expert subset and the disjoint contributions psum(model)-combine.

Dispatch is sort-based (linear), not one-hot einsum (quadratic in tokens):
top-k assignments are sorted by expert id, positions within an expert come
from a searchsorted over the sorted ids, capacity overflow drops (standard).
Router aux loss (switch-style load balance) is returned as a metric.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core.module import as_context, maybe_spamm_matmul, spamm_bmm_linear


def moe_params(key, cfg: MoEConfig, d_model: int, dtype, model_axis_size: int = 1):
    e = cfg.num_experts
    if cfg.impl == "ep":
        e = math.ceil(e / model_axis_size) * model_axis_size  # pad for EP
    ks = jax.random.split(key, 8)
    s_in, s_ff = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(cfg.expert_ff)
    p = {
        "router": jax.random.normal(ks[0], (d_model, cfg.num_experts), jnp.float32) * s_in,
        "w1": jax.random.normal(ks[1], (e, d_model, cfg.expert_ff), dtype) * s_in,
        "w3": jax.random.normal(ks[2], (e, d_model, cfg.expert_ff), dtype) * s_in,
        "w2": jax.random.normal(ks[3], (e, cfg.expert_ff, d_model), dtype) * s_ff,
    }
    if cfg.num_shared:
        p["shared"] = {
            "w1": jax.random.normal(ks[4], (d_model, cfg.shared_ff), dtype) * s_in,
            "w3": jax.random.normal(ks[5], (d_model, cfg.shared_ff), dtype) * s_in,
            "w2": jax.random.normal(ks[6], (cfg.shared_ff, d_model), dtype)
            * (1.0 / math.sqrt(cfg.shared_ff)),
            "gate": jax.random.normal(ks[7], (d_model, 1), jnp.float32) * s_in,
        }
    return p


def _dispatch(x, router_w, cfg: MoEConfig, capacity: int):
    """Local sort-based dispatch. x: (T, d). Returns routing tensors + aux."""
    t, d = x.shape
    k = cfg.top_k
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                            # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    flat_e = eidx.reshape(-1).astype(jnp.int32)                      # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(cfg.num_experts, dtype=jnp.int32),
                              side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    keep = pos < capacity

    # switch aux loss: E * sum_e f_e * P_e
    f = jnp.mean(jax.nn.one_hot(eidx[..., 0], cfg.num_experts, dtype=jnp.float32), 0)
    pbar = jnp.mean(probs, axis=0)
    aux = cfg.num_experts * jnp.sum(f * pbar)
    return se, st, sg, pos, keep, aux


def _grouped_ffn(buf, w1, w3, w2, act, spamm_cfg):
    """buf: (E_loc, C, d) → (E_loc, C, d) via per-expert SwiGLU.

    With SpAMM enabled and `moe_bmm` set, the three grouped GEMMs run as
    batched (E, C, d) @ (E, d, ff) products through `core.plan.spamm_bmm`:
    one get-norm pass per operand, per-expert gating, weight-side plans
    shared with the context's cache. Otherwise (default / training) each
    expert goes through the vmapped `spamm_linear` custom-vjp path."""
    cdt = buf.dtype
    ctx = as_context(spamm_cfg)

    if ctx is not None and ctx.enable and getattr(ctx.cfg, "moe_bmm", False):
        g = spamm_bmm_linear(buf, w1.astype(cdt), ctx)
        u = spamm_bmm_linear(buf, w3.astype(cdt), ctx)
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
        return spamm_bmm_linear(h, w2.astype(cdt), ctx)

    def one(b, w1e, w3e, w2e):
        g = maybe_spamm_matmul(b, w1e.astype(cdt), ctx)
        u = maybe_spamm_matmul(b, w3e.astype(cdt), ctx)
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
        return maybe_spamm_matmul(h, w2e.astype(cdt), ctx)

    return jax.vmap(one)(buf, w1, w3, w2)


def _shared_ffn(params, x, act, spamm_cfg):
    cdt = x.dtype
    g = maybe_spamm_matmul(x, params["w1"].astype(cdt), spamm_cfg)
    u = maybe_spamm_matmul(x, params["w3"].astype(cdt), spamm_cfg)
    h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
    out = maybe_spamm_matmul(h, params["w2"].astype(cdt), spamm_cfg)
    gate = jax.nn.sigmoid((x.astype(jnp.float32) @ params["gate"]))
    return out * gate.astype(cdt)


def moe_block(
    params: dict,
    x: jax.Array,             # (B, S, d), replicated over `model_axis`
    cfg: MoEConfig,
    act: str,
    *,
    mesh,
    batch_axes=("data",),
    model_axis: str = "model",
    spamm_cfg=None,
):
    """Returns (y, aux_loss). Runs as a shard_map over the full mesh."""
    b, s, d = x.shape
    nmodel = mesh.shape[model_axis]
    e_pad = params["w1"].shape[0]

    t_global = b * s
    ndata = 1
    for ax in (batch_axes or ()):
        ndata *= mesh.shape[ax]
    t_loc = t_global // ndata
    capacity = int(math.ceil(t_loc * cfg.top_k / cfg.num_experts * cfg.capacity_factor))
    capacity = max(4, -(-capacity // 4) * 4)

    if cfg.impl == "tp":
        w_specs = {
            "router": P(None, None),
            "w1": P(None, None, model_axis),
            "w3": P(None, None, model_axis),
            "w2": P(None, model_axis, None),
        }
    else:  # ep
        w_specs = {
            "router": P(None, None),
            "w1": P(model_axis, None, None),
            "w3": P(model_axis, None, None),
            "w2": P(model_axis, None, None),
        }
    if "shared" in params:
        w_specs["shared"] = {
            "w1": P(None, model_axis),
            "w3": P(None, model_axis),
            "w2": P(model_axis, None),
            "gate": P(None, None),
        }

    def local(p, xc):
        bl, sl, _ = xc.shape
        xt = xc.reshape(bl * sl, d)
        se, st, sg, pos, keep, aux = _dispatch(xt, p["router"], cfg, capacity)
        cdt = xc.dtype

        # NOTE on scatter indexing: over-capacity (and, in EP, foreign-expert)
        # tokens must be routed to OUT-OF-BOUNDS indices and dropped by
        # mode="drop". Clamping them onto a valid slot and writing zeros
        # would CLOBBER the legitimate token living in that slot (scatter
        # `set` order is unspecified) — a real bug this replaced.
        if cfg.impl == "tp":
            buf = jnp.zeros((e_pad, capacity, d), cdt)
            buf = buf.at[se, pos].set(xt[st], mode="drop")  # OOB pos dropped
            out = _grouped_ffn(buf, p["w1"], p["w3"], p["w2"], act, spamm_cfg)
            y = jnp.zeros((bl * sl, d), jnp.float32)
            y = y.at[st].add(
                out[se, jnp.minimum(pos, capacity - 1)].astype(jnp.float32)
                * (sg * keep)[:, None],   # dropped tokens contribute 0
                mode="drop",
            )
            y = jax.lax.psum(y, model_axis)  # combine ff-dim partials
        else:  # ep: each chip owns e_loc experts
            e_loc = e_pad // nmodel
            eoff = jax.lax.axis_index(model_axis) * e_loc
            le = se - eoff
            owned = (le >= 0) & (le < e_loc)
            mine = owned & keep
            buf = jnp.zeros((e_loc, capacity, d), cdt)
            buf = buf.at[jnp.where(owned, le, e_loc), pos].set(
                xt[st], mode="drop"   # foreign experts + OOB pos dropped
            )
            out = _grouped_ffn(buf, p["w1"], p["w3"], p["w2"], act, spamm_cfg)
            lec = jnp.clip(le, 0, e_loc - 1)
            y = jnp.zeros((bl * sl, d), jnp.float32)
            y = y.at[st].add(
                out[lec, jnp.minimum(pos, capacity - 1)].astype(jnp.float32)
                * (sg * mine)[:, None],   # foreign/dropped reads masked to 0
                mode="drop",
            )
            y = jax.lax.psum(y, model_axis)  # disjoint expert contributions

        if "shared" in p:
            ysh = _shared_ffn(p["shared"], xt, act, spamm_cfg)
            if cfg.impl == "tp":
                # shared ffn is ff-sharded too → its partial went into... no:
                # computed fully here with sharded w → psum needed
                ysh = jax.lax.psum(ysh.astype(jnp.float32), model_axis)
            else:
                ysh = jax.lax.psum(ysh.astype(jnp.float32), model_axis)
            y = y + ysh
        return y.reshape(bl, sl, d).astype(cdt), aux.reshape(1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(w_specs, P(batch_axes, None, None)),
        out_specs=(P(batch_axes, None, None), P(batch_axes)),
    )
    # expert GEMMs tap inside the shard_map trace: suspend any open trace
    # buffer so their tracers can't leak into an outer-trace carry (these
    # taps report through the callback path instead)
    sctx = as_context(spamm_cfg)
    saved = sctx.suspend_trace_buffer() if sctx is not None else None
    try:
        y, aux = fn(params, x)
    finally:
        if sctx is not None:
            sctx.resume_trace_buffer(saved)
    return y, jnp.mean(aux)
