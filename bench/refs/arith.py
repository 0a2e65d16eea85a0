"""The references' one matmul, at a named precision.

* "highest": f32 products (HIGHEST), what the references compute;
* "high": three bf16 passes (hi*hi + hi*lo + lo*hi), written out so it
  reads the same on any platform: the control one step below f32 at
  HIGHEST;
* "bf16": one bf16 pass, the step below that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high", "bf16")


def _split(x):
    """x = hi + lo + rest, hi and lo bf16. `reduce_precision` keeps the
    compiler from folding the f32 -> bf16 -> f32 round trip away."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def dot(spec: str, a, b, precision: str):
    """`jnp.einsum(spec, a, b)` in f32 at `precision`."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    ein = functools.partial(jnp.einsum, spec,
                            preferred_element_type=jnp.float32)
    if precision == "bf16":
        return ein(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return ein(ah, bh) + ein(ah, bl) + ein(al, bh)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")
