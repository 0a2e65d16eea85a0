"""The benchmark's own weights for the DeepSeek-V3-block configuration
(`bench/configs/moonlight-16b-a3b.json`), made on the device from the seed
in one jitted call. The system under test and the reference
(`bench.refs.deepseek_v3`) both read these; neither makes its own.

The layout is the one the serving engine reads: embedding, final norm,
unembedding, the leading dense layers stacked under "dense" and the MoE
layers under "layers". RMS norm gains are stored as offsets from 1 (the
engine applies x_hat * (1 + w)); the reference applies the gain 1 + w as
x_hat * gain, which is the same number.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.weights import key_from_seed

NORM_SCALE = 0.1      # gain = 1 + 0.1 x normal
BIAS_SCALE = 0.01     # e_score_correction_bias = 0.01 x normal


def _mix(normal, n, c) -> dict:
    d, h, m = c["d_model"], c["num_heads"], c["mla"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    lat = m["kv_lora_rank"]
    s = 1.0 / math.sqrt(d)
    return {
        "wq": normal((n, d, h * qk), s),
        "wkv_a": normal((n, d, lat + m["qk_rope_head_dim"]), s),
        "kv_ln": normal((n, lat), NORM_SCALE),
        "wkv_b": normal((n, lat, h * (m["qk_nope_head_dim"] + m["v_head_dim"])),
                        1.0 / math.sqrt(lat)),
        "wo": normal((n, h * m["v_head_dim"], d),
                     1.0 / math.sqrt(h * m["v_head_dim"])),
    }


def _ffn(normal, lead, d, ff) -> dict:
    return {"w1": normal((*lead, d, ff), 1.0 / math.sqrt(d)),
            "w3": normal((*lead, d, ff), 1.0 / math.sqrt(d)),
            "w2": normal((*lead, ff, d), 1.0 / math.sqrt(ff))}


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _weights(key, cfg_items):
    c = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    d, v = c["d_model"], c["vocab"]
    nd = c["first_dense"]
    nm = c["num_layers"] - nd
    moe = c["moe"]
    e = moe["held"] or moe["num_experts"]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    s = 1.0 / math.sqrt(d)
    return {
        "embed": {"embedding": normal((v, d), s)},
        "final_norm": normal((d,), NORM_SCALE),
        "unembed": {"kernel": normal((d, v), s)},
        "dense": {"ln1": normal((nd, d), NORM_SCALE),
                  "ln2": normal((nd, d), NORM_SCALE),
                  "mix": _mix(normal, nd, c),
                  "mlp": _ffn(normal, (nd,), d, c["d_ff"])},
        "layers": {
            "ln1": normal((nm, d), NORM_SCALE),
            "ln2": normal((nm, d), NORM_SCALE),
            "mix": _mix(normal, nm, c),
            "moe": {"router": normal((nm, d, moe["num_experts"]), s),
                    "score_bias": normal((nm, moe["num_experts"]), BIAS_SCALE),
                    **_ffn(normal, (nm, e), d, moe["expert_ff"]),
                    "shared": _ffn(normal, (nm,), d, moe["shared_ff"])},
        },
    }


def deepseek_v3(cfg: dict, seed: int):
    """f32 weights of the configuration's `model` group from `seed`."""
    keys = ("num_layers", "first_dense", "d_model", "num_heads", "d_ff",
            "vocab", "mla", "moe")
    items = tuple((k, tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
                   else cfg[k]) for k in keys)
    return _weights(key_from_seed(seed), items)
