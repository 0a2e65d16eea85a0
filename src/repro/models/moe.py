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
Router aux loss (switch-style load balance) is returned as a metric. This
capacity-dropping path trains the configs that hold all their experts.

Serving, and any config that holds a share of the experts (`MoEConfig.held`,
expert parallelism cut to one chip's share), runs `moe_share`: the layer is
told which experts it holds (ids [0, held)), routes over all of them, and
computes every routed row of its own experts with no capacity and no drop,
opening no shard_map and making no exchange. The routed rows, sorted by
expert with each expert's segment padded to whole row tiles, are processed
in fixed-size chunks with a data-dependent trip count, so memory stays
bounded whatever the skew; with SpAMM on, each chunk's three expert GEMMs
are one work-list kernel call each over all held experts
(`core.module.spamm_experts_frozen`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core.module import (as_context, collect_taps, maybe_spamm_matmul,
                               retap, spamm_bmm_linear, spamm_experts_frozen)


def moe_params(key, cfg: MoEConfig, d_model: int, dtype, model_axis_size: int = 1):
    e = cfg.num_experts
    if cfg.held:
        e = cfg.held                  # this chip's share
    elif cfg.impl == "ep":
        e = math.ceil(e / model_axis_size) * model_axis_size  # pad for EP
    ks = jax.random.split(key, 8)
    s_in, s_ff = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(cfg.expert_ff)
    p = {
        "router": jax.random.normal(ks[0], (d_model, cfg.num_experts), jnp.float32) * s_in,
        "w1": jax.random.normal(ks[1], (e, d_model, cfg.expert_ff), dtype) * s_in,
        "w3": jax.random.normal(ks[2], (e, d_model, cfg.expert_ff), dtype) * s_in,
        "w2": jax.random.normal(ks[3], (e, cfg.expert_ff, d_model), dtype) * s_ff,
    }
    if cfg.score_bias:
        p["score_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.num_shared:
        p["shared"] = {
            "w1": jax.random.normal(ks[4], (d_model, cfg.shared_ff), dtype) * s_in,
            "w3": jax.random.normal(ks[5], (d_model, cfg.shared_ff), dtype) * s_in,
            "w2": jax.random.normal(ks[6], (cfg.shared_ff, d_model), dtype)
            * (1.0 / math.sqrt(cfg.shared_ff)),
        }
        if cfg.shared_gate:
            p["shared"]["gate"] = jax.random.normal(
                ks[7], (d_model, 1), jnp.float32) * s_in
    return p


def _dispatch(x, p, cfg: MoEConfig, capacity: int):
    """Local sort-based dispatch. x: (T, d). Returns routing tensors + aux."""
    t, d = x.shape
    k = cfg.top_k
    probs = router_scores(p, x, cfg)                                 # (T, E)
    eidx, gates, _ = route(p, x, cfg, scores=probs)                  # (T, k)

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


def _grouped_ffn(buf, w1, w3, w2, act, ctx):
    """buf: (E_loc, C, d) → (E_loc, C, d) via per-expert SwiGLU.

    With SpAMM enabled and `moe_bmm` set, the three grouped GEMMs run as
    batched (E, C, d) @ (E, d, ff) products through `core.plan.spamm_bmm`:
    one get-norm pass per operand, per-expert gating, weight-side plans
    shared with the context's cache. Otherwise (default / training) each
    expert goes through the vmapped `spamm_linear` custom-vjp path; the
    experts' taps come out of the vmap as outputs (one row per expert) and
    are recorded into `ctx`'s open buffer."""
    cdt = buf.dtype

    if ctx is not None and ctx.enable and getattr(ctx.cfg, "moe_bmm", False):
        g = spamm_bmm_linear(buf, w1.astype(cdt), ctx)
        u = spamm_bmm_linear(buf, w3.astype(cdt), ctx)
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
        return spamm_bmm_linear(h, w2.astype(cdt), ctx)

    tctx = ctx if ctx is not None and ctx.is_collecting else None
    rows = []

    def ffn(b, w1e, w3e, w2e):
        g = maybe_spamm_matmul(b, w1e.astype(cdt), ctx, site="expert_w1")
        u = maybe_spamm_matmul(b, w3e.astype(cdt), ctx, site="expert_w3")
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
        return maybe_spamm_matmul(h, w2e.astype(cdt), ctx, site="expert_w2")

    def one(*operands):
        y, values, rows[:] = collect_taps(tctx, ffn, *operands)
        return y, values

    y, values = jax.vmap(one)(buf, w1, w3, w2)
    retap(tctx, values, rows)
    return y


def _shared_ffn(params, x, act, ctx):
    cdt = x.dtype
    g = maybe_spamm_matmul(x, params["w1"].astype(cdt), ctx, site="shared_w1")
    u = maybe_spamm_matmul(x, params["w3"].astype(cdt), ctx, site="shared_w3")
    h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
    out = maybe_spamm_matmul(h, params["w2"].astype(cdt), ctx,
                             site="shared_w2")
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

    ctx = as_context(spamm_cfg)
    tctx = ctx if ctx is not None and ctx.is_collecting else None
    rows = []

    def local(p, xc):
        # the expert GEMMs trace inside shard_map: they tap into a buffer
        # of this trace and leave it as an output, one row per shard
        (y, aux), values, rows[:] = collect_taps(tctx, ffn_local, p, xc)
        return y, aux, None if values is None else values[None]

    def ffn_local(p, xc):
        bl, sl, _ = xc.shape
        xt = xc.reshape(bl * sl, d)
        se, st, sg, pos, keep, aux = _dispatch(xt, p, cfg, capacity)
        cdt = xc.dtype

        # NOTE on scatter indexing: over-capacity (and, in EP, foreign-expert)
        # tokens must be routed to OUT-OF-BOUNDS indices and dropped by
        # mode="drop". Clamping them onto a valid slot and writing zeros
        # would CLOBBER the legitimate token living in that slot (scatter
        # `set` order is unspecified) — a real bug this replaced.
        if cfg.impl == "tp":
            buf = jnp.zeros((e_pad, capacity, d), cdt)
            buf = buf.at[se, pos].set(xt[st], mode="drop")  # OOB pos dropped
            out = _grouped_ffn(buf, p["w1"], p["w3"], p["w2"], act, ctx)
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
            out = _grouped_ffn(buf, p["w1"], p["w3"], p["w2"], act, ctx)
            lec = jnp.clip(le, 0, e_loc - 1)
            y = jnp.zeros((bl * sl, d), jnp.float32)
            y = y.at[st].add(
                out[lec, jnp.minimum(pos, capacity - 1)].astype(jnp.float32)
                * (sg * mine)[:, None],   # foreign/dropped reads masked to 0
                mode="drop",
            )
            y = jax.lax.psum(y, model_axis)  # disjoint expert contributions

        if "shared" in p:
            ysh = _shared_ffn(p["shared"], xt, act, ctx)
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
        out_specs=(P(batch_axes, None, None), P(batch_axes),
                   P(tuple(mesh.axis_names))),
    )
    y, aux, values = fn(params, x)
    retap(tctx, values, rows)
    return y, jnp.mean(aux)


# ---------------------------------------------------------------------------
# the expert layer that holds a share of the experts (serving path)
# ---------------------------------------------------------------------------

# routed rows one chunk of the expert layer covers (x and the FFN's three
# activations of one chunk stay a few hundred MB at d_model 2048)
CHUNK_ROWS = 16384
# row tile of the routed-row layout when SpAMM is off (no gate to align to)
DENSE_TILE = 16
# counters each share layer returns per step, in this order
COUNTERS = ("routed_rows", "busiest_rows", "empty_experts", "row_tiles",
            "dropped_rows")


def router_scores(p, x, cfg: MoEConfig):
    """(T, num_experts) f32 softmax or sigmoid scores of tokens x (T, d).
    The router is a plain f32 product: it decides selection, so it is never
    gated."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def route(p, x, cfg: MoEConfig, scores=None):
    """(ids (T, k) int32, weights (T, k) f32, margin (T,) f32) of tokens x
    (T, d) over all `num_experts` experts (`scores`: their
    `router_scores`, computed here when not given): the top-k by score
    plus the selection-only correction bias; weights the chosen scores
    normalised over the top-k, times `routed_scale`. `margin` is the k-th
    choice's lead over the next."""
    if scores is None:
        scores = router_scores(p, x, cfg)
    choice = scores + p["score_bias"] if cfg.score_bias else scores
    top, ids = jax.lax.top_k(choice, min(cfg.top_k + 1, cfg.num_experts))
    ids = ids[:, :cfg.top_k]
    margin = (top[:, cfg.top_k - 1] - top[:, cfg.top_k]
              if cfg.top_k < cfg.num_experts else top[:, -1])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * cfg.routed_scale, margin


def share_tiles(tokens: int, cfg: MoEConfig, tile: int) -> int:
    """Row tiles the routed-row layout can need at most: each held expert's
    rows padded to whole tiles, bounded both by the routed rows and by one
    expert seeing every token once."""
    e = cfg.held_experts
    kh = min(cfg.top_k, e)
    return min(tokens * kh // tile + e, e * -(-tokens // tile))


def _layout(ids, w, cfg: MoEConfig, tile: int, cap_tiles: int):
    """The routed rows of the held experts, sorted by expert, each expert's
    segment padded to whole row tiles: per layout row its token (-1 on
    padding) and weight, per row tile its expert, and the per-expert row
    counts and live tile count."""
    t, k = ids.shape
    e = cfg.held_experts
    flat_e = ids.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    key = jnp.where(flat_e < e, flat_e, e)
    counts = jnp.zeros(e + 1, jnp.int32).at[key].add(1)[:e]
    tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    order = jnp.argsort(key, stable=True)
    se = key[order]
    sec = jnp.clip(se, 0, e - 1)
    rank = (jnp.arange(t * k, dtype=jnp.int32)
            - (jnp.cumsum(counts) - counts)[sec])
    cap = cap_tiles * tile
    dest = jnp.where(se < e, (tile_end - tiles)[sec] * tile + rank, cap)
    row_tok = jnp.full(cap, -1, jnp.int32).at[dest].set(flat_t[order],
                                                        mode="drop")
    row_w = jnp.zeros(cap, jnp.float32).at[dest].set(
        w.reshape(-1)[order].astype(jnp.float32), mode="drop")
    tile_exp = jnp.clip(jnp.searchsorted(
        tile_end, jnp.arange(cap_tiles, dtype=jnp.int32), side="right"),
        0, e - 1).astype(jnp.int32)
    return row_tok, row_w, tile_exp, counts, tile_end[-1]


def _shared_share(params, x, act, ctx, frozen, gate: bool):
    """The shared experts, once, through the gated GEMMs (sites shared_w1,
    shared_w3, shared_w2), with qwen2-moe's sigmoid gate when held."""
    cdt = x.dtype
    fz = frozen or {}
    mm = functools.partial(maybe_spamm_matmul, spamm_cfg=ctx)
    g = mm(x, params["w1"].astype(cdt), frozen=fz.get("w1"), site="shared_w1")
    u = mm(x, params["w3"].astype(cdt), frozen=fz.get("w3"), site="shared_w3")
    h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
    out = mm(h, params["w2"].astype(cdt), frozen=fz.get("w2"),
             site="shared_w2")
    if gate and "gate" in params:
        out = out * jax.nn.sigmoid(
            x.astype(jnp.float32) @ params["gate"]).astype(cdt)
    return out


def moe_share(params: dict, x: jax.Array, cfg: MoEConfig, act: str, *,
              spamm_cfg=None, frozen=None):
    """The expert layer that holds experts [0, held) of `num_experts`.

    x: (B, S, d). Routes every token over all experts (`route`), computes
    every routed row of the held experts — no capacity, no drop — and adds
    the shared experts once. Returns (y (B, S, d), out) where `out` holds
    the step's counters (`COUNTERS`, int32 (5,)) and the routes (ids (B·S,
    top_k) int32).

    With SpAMM on and `frozen` holding the experts' plans
    (`plans.frozen.FrozenExperts` under w1/w3/w2), each chunk's three
    expert GEMMs run on the expert work-list kernel; otherwise each row
    tile multiplies by its expert's weight directly. With SpAMM on only
    the chunks that hold rows run, a data-dependent trip count; off (the
    training path), every chunk of the static bound runs, which keeps the
    layer differentiable."""
    ctx = as_context(spamm_cfg)
    gated = ctx is not None and ctx.enable
    fz = frozen or {}
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    ids, w, _ = route(params, xt, cfg)
    tile = ctx.cfg.tile if gated else DENSE_TILE
    cap = share_tiles(t, cfg, tile)
    ct = max(1, min(cap, CHUNK_ROWS // tile))
    n_max = -(-cap // ct)
    row_tok, row_w, tile_exp, counts, n_live = _layout(ids, w, cfg, tile,
                                                       n_max * ct)
    cdt = x.dtype
    sites = ("w1", "w3", "w2")
    frozen_ok = gated and all(fz.get(k) is not None for k in sites)

    def gemm(a, name, te, live):
        wt = params[name].astype(cdt)
        if frozen_ok:
            return spamm_experts_frozen(a, wt, fz[name], te, live)
        r = a.shape[0]
        y = jnp.einsum("itk,ikn->itn", a.reshape(r // tile, tile, -1),
                       wt[te]).reshape(r, -1)
        z = jnp.int32(0)
        return y, z, z, z

    def chunk(c, carry):
        y, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(row_tok, c * ct * tile, ct * tile)
        rw = jax.lax.dynamic_slice_in_dim(row_w, c * ct * tile, ct * tile)
        te = jax.lax.dynamic_slice_in_dim(tile_exp, c * ct, ct)
        live = jnp.clip(n_live - c * ct, 0, ct)
        ok = rows >= 0
        a = jnp.where(ok[:, None], xt[jnp.maximum(rows, 0)], 0).astype(cdt)
        g, *s1 = gemm(a, "w1", te, live)
        u, *s3 = gemm(a, "w3", te, live)
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
        o, *s2 = gemm(h, "w2", te, live)
        y = y.at[jnp.where(ok, rows, t)].add(
            o.astype(jnp.float32) * rw[:, None], mode="drop")
        step = jnp.stack([jnp.sum(ok, dtype=jnp.int32), *s1, *s3, *s2])
        return y, acc + step

    n_chunks = (n_live + ct - 1) // ct if gated else n_max
    y, acc = jax.lax.fori_loop(
        0, n_chunks, chunk,
        (jnp.zeros((t, d), jnp.float32), jnp.zeros(10, jnp.int32)))
    if frozen_ok and ctx.is_collecting:
        # one tap per site after the loop: the loop body's traced values
        # cannot leave it any other way
        tile_bytes = 4.0 * tile * tile
        for i, site in enumerate(sites):
            valid, gate, steps = (acc[1 + 3 * i + j].astype(jnp.float32)
                                  for j in range(3))
            ctx.tap(valid / jnp.maximum(gate, 1.0),
                    tile_bytes * (2.0 * steps * fz[site].kb
                                  + n_live.astype(jnp.float32)
                                  * fz[site].gn),
                    site=f"expert_{site}")
    if "shared" in params:
        y = y + _shared_share(params["shared"], xt, act, ctx,
                              fz.get("shared"),
                              cfg.shared_gate).astype(jnp.float32)
    routed = jnp.sum(counts)
    stats = jnp.stack([routed, jnp.max(counts),
                       jnp.sum(counts == 0, dtype=jnp.int32), n_live,
                       routed - acc[0]]).astype(jnp.int32)
    return y.reshape(b, s, d).astype(cdt), {"counts": stats, "routes": ids}
