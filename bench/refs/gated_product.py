"""Plain reference of the norm-gated product (SpAMM): C = sum over the
(i, j, k) tile triples whose Frobenius-norm product ||A_ik|| ||B_kj||
reaches tau of A_ik @ B_kj. Straightforward `jax.numpy` in f32; it imports
nothing of the system under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.arith import dot

# tau sits between two tile-norm products that differ by at least this
# share, so norms that differ in their last bits (kernel against
# reference, one device against four) keep the same tiles
GAP = 1e-3


@functools.partial(jax.jit, static_argnames=("tile",))
def tile_norms(x, tile: int):
    """(M // tile, K // tile) Frobenius norms of the tiles of x."""
    m, k = x.shape
    t = x.reshape(m // tile, tile, k // tile, tile)
    return jnp.sqrt(jnp.sum(t * t, axis=(1, 3)))


def gate(na, nb, tau: float) -> np.ndarray:
    """(gm, gn, gk) bool: which triples survive, in float64 on the host."""
    na = np.asarray(na, np.float64)
    nb = np.asarray(nb, np.float64)
    return na[:, None, :] * nb.T[None] >= tau


def choose_tau(na, nb, valid_ratio: float) -> float:
    """The tau that keeps the share of triples nearest `valid_ratio`, set in
    the middle (geometric) of a gap of at least GAP between neighbouring
    norm products, so rounding of the norms cannot flip a tile."""
    prods = np.sort((np.asarray(na, np.float64)[:, None, :]
                     * np.asarray(nb, np.float64).T[None]).ravel())
    vals, counts = np.unique(prods, return_counts=True)
    kept = np.cumsum(counts[::-1])[::-1]     # triples >= vals[i]
    wide = vals[1:] >= vals[:-1] * (1.0 + GAP)
    cand = np.flatnonzero(wide) + 1           # tau just below vals[cand]
    if cand.size == 0:
        raise ValueError("no gap between tile-norm products to put tau in")
    best = cand[np.argmin(np.abs(kept[cand] / prods.size - valid_ratio))]
    return float(np.sqrt(vals[best] * vals[best - 1]))


@functools.partial(jax.jit, static_argnames=("tile", "precision"))
def product(a, b, mask, tile: int, precision: str = "highest"):
    """C of the gated product; `mask` (gm, gn, gk) bool from `gate`. Runs
    one contraction tile at a time: the dense (M, tile) @ (tile, N) slab,
    masked to its surviving (i, j) tiles, added to C."""
    m, n = a.shape[0], b.shape[1]
    gm, gn = m // tile, n // tile

    def body(k, c):
        ak = jax.lax.dynamic_slice_in_dim(a, k * tile, tile, axis=1)
        bk = jax.lax.dynamic_slice_in_dim(b, k * tile, tile, axis=0)
        p = dot("mt,tn->mn", ak, bk, precision).reshape(gm, tile, gn, tile)
        keep = jax.lax.dynamic_index_in_dim(mask, k, axis=2, keepdims=False)
        return c + jnp.where(keep[:, None, :, None], p, 0.0).reshape(m, n)

    return jax.lax.fori_loop(0, mask.shape[2], body,
                             jnp.zeros((m, n), jnp.float32))
