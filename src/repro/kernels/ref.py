"""Pure-jnp oracles for the two cuSpAMM kernels (paper §3.2, §3.3).

These are the ground-truth references every Pallas kernel is tested against
(interpret=True on CPU, compiled on TPU). They are also the "jnp backend"
used by the model stack during the CPU dry-run, where Pallas TPU kernels
cannot lower.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tile_norms_ref(x: jax.Array, tile: int) -> jax.Array:
    """Per-tile Frobenius norms (paper Eq. 2, the `normmap`).

    x: (M, K) array, M % tile == 0 and K % tile == 0 (pad upstream).
    Returns (M//tile, K//tile) float32 norms.
    """
    m, k = x.shape
    bm, bk = m // tile, k // tile
    x4 = x.astype(jnp.float32).reshape(bm, tile, bk, tile)
    return jnp.sqrt(jnp.einsum("itjs,itjs->ij", x4, x4))


def pool_norms_ref(normmap: jax.Array, factor: int = 2) -> jax.Array:
    """One norm-pyramid coarsening step: sqrt-of-sumsq `factor`×`factor`
    pooling of a normmap (paper Eq. 2 applied at the next tile size up).

    Because ‖X‖_F² of a coarse tile is exactly the sum of its sub-tiles'
    ‖·‖_F², pooling the *squares* reuses the finest get-norm pass — no second
    sweep over the matrix — and the coarse entry upper-bounds every
    descendant tile norm (the exactness lever of hierarchical gating).

    Supports leading batch dims; the trailing two dims are zero-padded to
    `factor` multiples (zero tiles contribute nothing to the sumsq).
    """
    g1, g2 = normmap.shape[-2:]
    p1, p2 = (-g1) % factor, (-g2) % factor
    if p1 or p2:
        pad = [(0, 0)] * (normmap.ndim - 2) + [(0, p1), (0, p2)]
        normmap = jnp.pad(normmap, pad)
    c1, c2 = (g1 + p1) // factor, (g2 + p2) // factor
    sq = (normmap * normmap).reshape(
        *normmap.shape[:-2], c1, factor, c2, factor
    )
    return jnp.sqrt(jnp.sum(sq, axis=(-3, -1)))


def spamm_mask_ref(norm_a: jax.Array, norm_b: jax.Array, tau: jax.Array) -> jax.Array:
    """bitmap[i, j, k] = normA[i,k] * normB[k,j] >= tau  (paper Alg. 2 lines 3-8)."""
    prod = norm_a[:, None, :] * jnp.swapaxes(norm_b, 0, 1)[None, :, :]
    return prod >= tau


def spamm_matmul_ref(
    a: jax.Array,
    b: jax.Array,
    tau,
    tile: int,
    *,
    precision=None,
    mask=None,
) -> jax.Array:
    """Reference SpAMM: C[i,j] = sum_k bitmap[i,j,k] * A[i,k] @ B[k,j].

    a: (M, K), b: (K, N); M, K, N divisible by `tile`.
    Computed as a dense blocked einsum with the mask applied to A-blocks —
    mathematically identical to skipping the products. `mask` (gm, gn, gk)
    replaces the τ-test bitmap (tau is then ignored): a kernel given a
    plan's work-list is checked against the product over that same set.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    gm, gk, gn = m // tile, k // tile, n // tile
    if mask is None:
        na = tile_norms_ref(a, tile)  # (gm, gk)
        nb = tile_norms_ref(b, tile)  # (gk, gn)
        mask = spamm_mask_ref(na, nb, jnp.asarray(tau, jnp.float32))
    a4 = a.reshape(gm, tile, gk, tile)
    b4 = b.reshape(gk, tile, gn, tile)
    # out[i p, j q] = sum_{k, s} mask[i,j,k] a[i,p,k,s] b[k,s,j,q]
    out = jnp.einsum(
        "ijk,ipks,ksjq->ipjq",
        mask.astype(a.dtype),
        a4,
        b4,
        precision=precision,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(m, n).astype(jnp.promote_types(a.dtype, jnp.float32))


def spamm_compact_ref(mask: jax.Array):
    """Compact valid-k lists (the paper's `map_offset`, Fig. 3b) — jnp version.

    mask: (gm, gn, gk) bool.
    Returns (kidx, nvalid):
      kidx   (gm, gn, gk) int32 — first nvalid entries are the valid k's in
             ascending order; padding slots repeat the last valid k (or 0 if
             none), so a Pallas index_map revisits the same block (no re-fetch).
      nvalid (gm, gn) int32 — number of valid k's (the paper's validNum).
    """
    gm, gn, gk = mask.shape
    ks = jnp.arange(gk, dtype=jnp.int32)
    nvalid = jnp.sum(mask, axis=-1, dtype=jnp.int32)  # (gm, gn)
    # invalid slots get sentinel gk, sort ascending -> valid ks first, in order
    sentinel = jnp.where(mask, ks[None, None, :], jnp.int32(gk))
    kidx = jnp.sort(sentinel, axis=-1)
    last = jnp.take_along_axis(
        kidx, jnp.maximum(nvalid - 1, 0)[..., None].astype(jnp.int32), axis=-1
    )
    last = jnp.where(nvalid[..., None] > 0, last, 0).astype(jnp.int32)
    t = ks[None, None, :]
    kidx = jnp.where(t < nvalid[..., None], kidx, last).astype(jnp.int32)
    return kidx, nvalid
