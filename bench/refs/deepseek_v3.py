"""Plain reference of the DeepSeek-V3 block the MoE serving cell runs
(`bench/configs/moonlight-16b-a3b.json`): one sequence's forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no batching.

It follows `transformers`' `modeling_deepseek_v3.py` (DeepseekV3ForCausalLM
as of transformers 4.57):

* pre-norm RMS layers, x_hat * gain; the weights store gain - 1
  (`bench.deepseek_weights`), so the gain is 1 + w;
* latent attention decompressed, as the HF file computes it: q_proj;
  kv_a_proj_with_mqa into the latent and one rotary key; kv_a_layernorm on
  the latent; kv_b_proj into per-head keys and values; interleaved rotary
  positions on the rope dims (`apply_rotary_pos_emb_interleave`); causal
  softmax at qk_head_dim ** -0.5; o_proj;
* the leading dense layers' SwiGLU MLP;
* the MoE layers' router: sigmoid scores, the top-k of score plus the
  correction bias (n_group = topk_group = 1 leaves every expert eligible),
  weights the chosen scores normalised over the top-k and times the routed
  scale; the held experts' SwiGLU outputs weighted and summed (the absent
  experts' part lies on other chips, left out here as in the program); the
  shared experts added once;
* the final norm and the unembedding over the vocabulary slice.

`routes` (MoE layers, T, top_k) forces the selections (the weights still
come from this pass's own scores); the pass always returns its own top-k
and the margin between its k-th and (k+1)-th choice. It imports nothing of
the system under test. `precision` is one of `bench.refs.arith.PRECISIONS`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.refs.arith import dot as _dot


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope_interleave(x, theta):
    """x: (T, H, D). The HF file's interleaved rotary embedding: pairs
    (x0, x1), (x2, x3), ... regrouped into halves, then rotate_half."""
    t, d = x.shape[0], x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def attention(x, p, c, dot):
    t = x.shape[0]
    h, m = c["num_heads"], c["mla"]
    nope, rope, lat = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                       m["kv_lora_rank"])
    q = dot("td,de->te", x, p["wq"]).reshape(t, h, nope + rope)
    q_pass, q_rot = q[..., :nope], q[..., nope:]
    ckv = dot("td,de->te", x, p["wkv_a"])
    k_pass = rms_norm(ckv[:, :lat], p["kv_ln"], c["norm_eps"])
    k_rot = ckv[:, None, lat:]
    kv = dot("tc,ce->te", k_pass, p["wkv_b"]).reshape(t, h, -1)
    k_pass, v = kv[..., :nope], kv[..., nope:]
    q_rot = rope_interleave(q_rot, c["rope_theta"])
    k_rot = jnp.broadcast_to(rope_interleave(k_rot, c["rope_theta"]),
                             (t, h, rope))
    q = jnp.concatenate([q_pass, q_rot], -1)
    k = jnp.concatenate([k_pass, k_rot], -1)
    s = dot("qhd,khd->hqk", q, k) * (nope + rope) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("hqk,khd->qhd", a, v).reshape(t, -1)
    return dot("te,ed->td", o, p["wo"])


def mlp(x, p, dot):
    g = dot("td,df->tf", x, p["w1"])
    u = dot("td,df->tf", x, p["w3"])
    return dot("tf,fd->td", jax.nn.silu(g) * u, p["w2"])


def moe(x, p, c, dot, routes):
    """(out, own top-k (T, k), margin (T,)); `routes` (T, k) or None."""
    mc = c["moe"]
    k = mc["top_k"]
    scores = jax.nn.sigmoid(dot("td,de->te", x, p["router"]))
    choice = scores + p["score_bias"]
    top, own = jax.lax.top_k(choice, k + 1)
    margin = top[:, k - 1] - top[:, k]
    own = own[:, :k]
    sel = own if routes is None else routes
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * mc["routed_scale"]
    out = mlp(x, p["shared"], dot)
    for e in range(p["w1"].shape[0]):          # the held experts
        we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        pe = {n: p[n][e] for n in ("w1", "w3", "w2")}
        out = out + we[:, None] * mlp(x, pe, dot)
    return out, own.astype(jnp.int32), margin


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "precision", "forced"))
def _forward(params, tokens, routes, cfg_items, precision, forced):
    c = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    dot = functools.partial(_dot, precision=precision)
    eps = c["norm_eps"]
    x = params["embed"]["embedding"][tokens]

    def dense(x, p):
        x = x + attention(rms_norm(x, p["ln1"], eps), p["mix"], c, dot)
        return x + mlp(rms_norm(x, p["ln2"], eps), p["mlp"], dot), None

    def sparse(x, pr):
        p, r = pr
        x = x + attention(rms_norm(x, p["ln1"], eps), p["mix"], c, dot)
        f, own, margin = moe(rms_norm(x, p["ln2"], eps), p["moe"], c, dot,
                             r if forced else None)
        return x + f, (own, margin)

    x, _ = jax.lax.scan(dense, x, params["dense"])
    x, (own, margin) = jax.lax.scan(sparse, x, (params["layers"], routes))
    x = rms_norm(x, params["final_norm"], eps)
    return dot("td,dv->tv", x, params["unembed"]["kernel"]), own, margin


def forward(params, tokens, cfg: dict, routes=None,
            precision: str = "highest"):
    """One sequence: tokens (T,) -> (logits (T, vocab) f32, own routes
    (MoE layers, T, top_k) int32, margins (MoE layers, T) f32). `routes`,
    when given, forces each MoE layer's selections."""
    keys = ("num_heads", "norm_eps", "rope_theta", "mla", "moe")
    items = tuple((k, tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
                   else cfg[k]) for k in keys)
    tokens = jnp.asarray(tokens, jnp.int32)
    forced = routes is not None
    if not forced:
        routes = jnp.zeros((params["layers"]["ln1"].shape[0],
                            tokens.shape[0], cfg["moe"]["top_k"]), jnp.int32)
    return _forward(params, tokens, jnp.asarray(routes, jnp.int32), items,
                    precision, forced)
