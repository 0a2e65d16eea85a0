"""Operations of the DeepSeek-V3-block serving cell, from shapes and the
expert layers' counted rows alone (the yardstick's counts, as `bench.work`
gives them for the dense decoder).

A step over `rows` token rows runs, per layer: the latent-attention
projections (wq, wkv_a, wo; wkv_b decompresses the latent in prefill only,
a decode step absorbs it into per-head products instead); the dense
layers' SwiGLU; per MoE layer the router, the shared experts, and three
expert GEMMs over the rows routed to held experts. Attention's core counts
its scores and weighted sums over each token's context.
"""
from __future__ import annotations

import numpy as np


def _dims(model: dict):
    m = model["mla"]
    return (model["d_model"], model["num_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def gemm_shapes(model: dict, rows: int, decode: bool) -> list:
    """(rows, k, n) of the gated GEMMs one step runs over `rows` token
    rows, expert GEMMs excluded: per layer wq, wkv_a, (wkv_b in prefill),
    wo, then the dense layers' w1, w3, w2 or the MoE layers' shared w1, w3,
    w2."""
    d, h, lat, nope, rope, v = _dims(model)
    moe = model["moe"]
    attn = [(rows, d, h * (nope + rope)), (rows, d, lat + rope)]
    if not decode:
        attn.append((rows, lat, h * (nope + v)))
    attn.append((rows, h * v, d))
    nd = model["first_dense"]
    ff, sff = model["d_ff"], moe["shared_ff"]
    dense = [(rows, d, ff), (rows, d, ff), (rows, ff, d)]
    shared = [(rows, d, sff), (rows, d, sff), (rows, sff, d)]
    return ((attn + dense) * nd
            + (attn + shared) * (model["num_layers"] - nd))


def _core_flops(model: dict, context: np.ndarray, decode: bool) -> float:
    """Attention's per-token products over `context` positions each, and a
    decode step's absorbed per-head products, summed over the layers."""
    d, h, lat, nope, rope, v = _dims(model)
    ctx = float(np.asarray(context, np.float64).sum())
    n = np.asarray(context).size
    if decode:    # scores over latents and rotary keys, sum over latents
        per = 2.0 * h * ctx * (lat + rope + lat) + 2.0 * n * h * lat * (nope + v)
    else:         # decompressed scores and weighted values
        per = 2.0 * h * ctx * (nope + rope + v)
    return per * model["num_layers"]


def step_flops(model: dict, context: np.ndarray, decode: bool,
               routed_rows: int, logits_rows: int) -> float:
    """Active FLOPs of one step over tokens at `context` positions each:
    the gated GEMMs, attention's core, the routers, `routed_rows` rows
    through the three expert GEMMs, and `logits_rows` rows of the
    unembedding."""
    rows = int(np.asarray(context).size)
    d, moe = model["d_model"], model["moe"]
    gemms = sum(2.0 * m * k * n for m, k, n in gemm_shapes(model, rows,
                                                           decode))
    router = 2.0 * rows * d * moe["num_experts"] * (
        model["num_layers"] - model["first_dense"])
    experts = 2.0 * 3 * routed_rows * d * moe["expert_ff"]
    unembed = 2.0 * logits_rows * d * model["vocab"]
    return (gemms + _core_flops(model, context, decode) + router + experts
            + unembed)


def wave_flops(model: dict, wave: dict) -> float:
    """Active FLOPs of one serving wave, its expert rows as the engine
    counted them (`wave["moe"]`)."""
    b, p, n = wave["batch"], wave["prompt_len"], wave["new_tokens"]
    moe = wave["moe"]
    pre = np.tile(np.arange(1, p + 1), b)
    flops = step_flops(model, pre, False, moe["prefill"]["routed_rows"], b)
    if n > 1:
        dec = np.repeat(p + np.arange(1, n), b)
        flops += step_flops(model, dec, True, moe["decode"]["routed_rows"],
                            b * (n - 1))
    return flops

