"""Plain reference of the decoder the serving cells run: the forward pass
in straightforward `jax.numpy`, float32, no kernels, no cache, no gating.

It follows the equations of the configuration file (`bench/configs/`),
including what the file lists under `assumed`: pre-norm RMS layers with a
(1 + w) gain, rotary positions on q and k (half-split), causal softmax
attention with 1/sqrt(head_dim) scaling, a GELU (tanh form) MLP, a final
RMS norm and an unembedding. It imports nothing of the system under test;
the weights it reads are the benchmark's own (`bench.weights`).

`precision` is one of `bench.refs.arith.PRECISIONS`; every matmul goes
through `arith.dot`, so one switch moves them all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.refs.arith import dot as _dot


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, theta):
    """x: (B, T, H, D); rotate the two halves of D by position."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, cfg: dict, precision: str):
    """One residual layer on (B, T, d)."""
    b, t, _ = x.shape
    h, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    dot = functools.partial(_dot, precision=precision)
    y = rms_norm(x, p["ln1"], cfg["norm_eps"])
    q = dot("btd,de->bte", y, p["mix"]["wq"]).reshape(b, t, h, hd)
    k = dot("btd,de->bte", y, p["mix"]["wk"]).reshape(b, t, kvh, hd)
    v = dot("btd,de->bte", y, p["mix"]["wv"]).reshape(b, t, kvh, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    s = dot("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("bhqk,bkhd->bqhd", a, v).reshape(b, t, h * hd)
    x = x + dot("bte,ed->btd", o, p["mix"]["wo"])
    y = rms_norm(x, p["ln2"], cfg["norm_eps"])
    f = jax.nn.gelu(dot("btd,df->btf", y, p["mlp"]["w1"]), approximate=True)
    return x + dot("btf,fd->btd", f, p["mlp"]["w2"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits(params, tokens, cfg_items, precision):
    cfg = dict(cfg_items)
    x = params["embed"]["embedding"][tokens]

    def body(x, p):
        return layer(x, p, cfg, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    return _dot("btd,dv->btv", x, params["unembed"]["kernel"], precision)


def logits(params, tokens, cfg: dict, precision: str = "highest"):
    """(B, T, vocab) f32 logits at every position of `tokens` (B, T). The
    layers run one at a time under a scan, so the peak is one layer's
    activations beside the weights."""
    keys = ("num_heads", "num_kv_heads", "head_dim", "rope_theta", "norm_eps")
    items = tuple((k, cfg[k]) for k in keys)
    return _logits(params, jnp.asarray(tokens, jnp.int32), items, precision)
