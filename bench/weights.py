"""The benchmark's own weights and operands, made on the device from the
seed in one jitted call each. The system under test and the references
both read these; neither makes its own.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, *salt: int):
    """A PRNG key from any whole seed (wider than 32 bits too) and salt."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    for part in (seed >> 32, *salt):
        key = jax.random.fold_in(key, np.uint32(int(part) & 0xFFFFFFFF))
    return key


@functools.partial(jax.jit, static_argnames=("shape_items",))
def _transformer(key, shape_items):
    c = dict(shape_items)
    L, d, ff, v = c["num_layers"], c["d_model"], c["d_ff"], c["vocab"]
    hq = c["num_heads"] * c["head_dim"]
    hk = c["num_kv_heads"] * c["head_dim"]
    ks = iter(jax.random.split(key, 11))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    s = 1.0 / math.sqrt(d)
    return {
        "embed": {"embedding": normal((v, d), s)},
        "final_norm": normal((d,), 0.1),
        "unembed": {"kernel": normal((d, v), s)},
        "layers": {
            "ln1": normal((L, d), 0.1),
            "ln2": normal((L, d), 0.1),
            "mix": {"wq": normal((L, d, hq), s), "wk": normal((L, d, hk), s),
                    "wv": normal((L, d, hk), s),
                    "wo": normal((L, hq, d), 1.0 / math.sqrt(hq))},
            "mlp": {"w1": normal((L, d, ff), s),
                    "w2": normal((L, ff, d), 1.0 / math.sqrt(ff))},
        },
    }


def transformer(cfg: dict, seed: int):
    """f32 weights of a pre-norm decoder in the layout the serving engine
    reads: embedding, final norm, unembedding, and the layers stacked on a
    leading depth axis. Norm gains are small random values, so a path that
    dropped one would show."""
    keys = ("num_layers", "d_model", "d_ff", "vocab", "num_heads",
            "num_kv_heads", "head_dim")
    return _transformer(key_from_seed(seed), tuple((k, cfg[k]) for k in keys))


@functools.partial(jax.jit, static_argnames=("n", "count", "c", "lam"))
def _decay(key, n, count, c, lam):
    i = jnp.arange(n, dtype=jnp.float32)
    d = jnp.abs(i[:, None] - i[None, :])
    mag = c / (d ** lam + 1.0)
    return tuple(mag * jax.random.rademacher(k, (n, n), jnp.float32)
                 for k in jax.random.split(key, count))


def decay_matrices(n: int, count: int, seed: int, *, c: float, lam: float):
    """A tuple of `count` (n, n) f32 matrices a_ij = c / (|i - j|^lam + 1)
    with signs from the seed (the paper's section 4.1 synthesized decay
    matrix). The magnitudes, and so every tile norm, are the same for every
    seed."""
    return _decay(key_from_seed(seed, 1), n, count, float(c), float(lam))
