"""SpAMM core — the paper's contribution as a composable JAX module.

Functional API over the plan/execute pipeline (repro.core.plan) with:
  * arbitrary (M, K) @ (K, N) shapes (auto zero-padding to tile multiples,
    paper §3 "the matrices are padded with zeros"),
  * tau- or valid-ratio-driven gating (ratio → tau via core.tau_search),
  * the original *recursive* Algorithm 1 as an oracle for the equivalence
    property test (paper §3.1 claims re-design ≡ recursion),
  * scalable valid-ratio counting that never materializes the O(BDIM³)
    product tensor (sorted normmap + searchsorted).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as _plan
from repro.core.plan import SpammInfo, pad_to_tile  # re-exported API
from repro.obs.tracer import annotate


# ---------------------------------------------------------------------------
# scalable valid-ratio counting (no O(gm·gn·gk) tensor)
# ---------------------------------------------------------------------------

def count_valid(norm_a: jax.Array, norm_b: jax.Array, tau) -> jax.Array:
    """#{(i,j,k): na[i,k]·nb[k,j] >= tau} in O(gm·gk·log gn) memory-light form.

    The count can exceed int32 for production grids — gm·gk·gn overflows 2³¹
    already at gm = gk = gn = 1290, i.e. an N ≈ 82k matrix at tile 64. When
    the grid makes overflow possible the sum falls back to i64 (f32 without
    jax_enable_x64 — approximate above 2²⁴ but monotone, which is all the
    τ-bisection needs); smaller grids keep the exact int32 sum.
    """
    gm, gk = norm_a.shape
    gk2, gn = norm_b.shape
    assert gk == gk2
    tau = jnp.asarray(tau, jnp.float32)
    sorted_nb = jnp.sort(norm_b, axis=1)  # (gk, gn)
    # threshold per (i, k): nb >= tau / na  (na==0 ⇒ nothing passes unless tau<=0)
    thr = tau / jnp.maximum(norm_a, 1e-38)  # (gm, gk)
    counts = jax.vmap(
        lambda row, t: gn - jnp.searchsorted(row, t, side="left"),
        in_axes=(0, 1),
        out_axes=1,
    )(sorted_nb, thr)  # (gm, gk), each entry <= gn (int32-safe)
    # na == 0: products are 0; valid iff tau <= 0
    zero_a = norm_a <= 0.0
    counts = jnp.where(zero_a, jnp.where(tau <= 0.0, gn, 0), counts)
    if gm * gk * gn < 2 ** 31:
        return jnp.sum(counts, dtype=jnp.int32)  # exact
    acc = jnp.int64 if jax.config.read("jax_enable_x64") else jnp.float32
    return jnp.sum(counts.astype(acc))


def valid_ratio_of(norm_a: jax.Array, norm_b: jax.Array, tau) -> jax.Array:
    """paper §3.5.2: valid ratio = Σ V[i,j] / BDIM³ (generalized to gm·gn·gk).

    The denominator is formed as a python float: gm·gk·gn overflows int32
    for large grids long before the arrays themselves are a problem.
    """
    gm, gk = norm_a.shape
    _, gn = norm_b.shape
    return count_valid(norm_a, norm_b, tau) / (float(gm) * float(gk) * float(gn))


# ---------------------------------------------------------------------------
# top-level SpAMM
# ---------------------------------------------------------------------------

def spamm(
    a: jax.Array,
    b: jax.Array,
    tau=None,
    *,
    valid_ratio=None,
    tile: int = 64,
    block_n: int = 1,
    backend: str = "auto",
    use_mxu_norm: bool = False,
    out_dtype=None,
    compute_dtype: str = "float32",
):
    """C ≈ A @ B with norm-gated tile skipping. Returns (C, SpammInfo).

    Exactly one of `tau` / `valid_ratio` must be given. Arbitrary shapes are
    zero-padded to tile multiples (paper §3) and the result is un-padded.
    One-shot plan+execute; to amortize the gating phase across repeated
    products, build the plan once with `repro.core.plan.plan` and call
    `repro.core.plan.execute` per product.

    `compute_dtype` selects the GEMM operand precision (float32 | bfloat16 |
    int8); accumulation is always f32 and the gate stays a superset of the
    f32 gate (norms from the quantized view, τ widened by the analytic
    quantization bound — repro.kernels.quantize).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    # the weight side pads N to tile·block_n, not just tile: super-column
    # grouping needs gn % block_n == 0 for ANY N (padded columns have zero
    # norms, so they never flip a super-column's gate on their own)
    ap, bp = pad_to_tile(a, tile), pad_to_tile(b, tile, tile * block_n)

    p = _plan.plan(
        ap, bp, tau,
        valid_ratio=valid_ratio,
        tile=tile, block_n=block_n, backend=backend,
        use_mxu_norm=use_mxu_norm,
        compute_dtype=compute_dtype,
    )
    with annotate("spamm_execute"):
        c = _plan.execute(p, ap, bp, out_dtype=out_dtype)[:m, :n]
    frac = p.valid_fraction
    return c, SpammInfo(
        tau=p.tau,
        valid_fraction=frac,
        effective_flops=frac * (2.0 * m * k * n),
    )


# ---------------------------------------------------------------------------
# original recursive Algorithm 1 (oracle for the equivalence test)
# ---------------------------------------------------------------------------

def recursive_spamm(a: np.ndarray, b: np.ndarray, tau: float, leaf: int) -> np.ndarray:
    """Paper Algorithm 1, verbatim quad-tree recursion (numpy, test oracle).

    Square matrices with N a power-of-two multiple of `leaf`.
    """
    n = a.shape[0]
    assert a.shape == b.shape == (n, n)

    def fnorm(x):
        return float(np.sqrt(np.sum(np.asarray(x, np.float64) ** 2)))

    def rec(ab, bb):
        nn = ab.shape[0]
        if nn == leaf:
            return np.asarray(ab, np.float64) @ np.asarray(bb, np.float64)
        h = nn // 2
        c = np.zeros((nn, nn), np.float64)
        for i in (0, 1):
            for j in (0, 1):
                acc = np.zeros((h, h), np.float64)
                for k in (0, 1):
                    asub = ab[i * h:(i + 1) * h, k * h:(k + 1) * h]
                    bsub = bb[k * h:(k + 1) * h, j * h:(j + 1) * h]
                    if fnorm(asub) * fnorm(bsub) >= tau:
                        acc += rec(asub, bsub)
                c[i * h:(i + 1) * h, j * h:(j + 1) * h] = acc
        return c

    return rec(a, b)


# ---------------------------------------------------------------------------
# decay-matrix generators (paper §2.1 / §4.1)
# ---------------------------------------------------------------------------

def algebraic_decay(n: int, c: float = 0.1, lam: float = 0.1, seed=None) -> np.ndarray:
    """a_ij = c / (|i-j|^lam + 1); with seed, sign-randomized (keeps |a_ij|)."""
    d = np.abs(np.arange(n)[:, None] - np.arange(n)[None, :]).astype(np.float64)
    m = (c / (d ** lam + 1.0)).astype(np.float32)
    if seed is not None:
        rng = np.random.default_rng(seed)
        m = m * rng.choice(np.float32([-1.0, 1.0]), size=m.shape)
    return m


def exponential_decay(n: int, c: float = 1.0, lam: float = 0.9, seed=None) -> np.ndarray:
    """|a_ij| <= c·lam^|i-j| (ergo-style matrices in §4.3.1 decay this way)."""
    d = np.abs(np.arange(n)[:, None] - np.arange(n)[None, :]).astype(np.float64)
    m = (c * np.power(lam, d)).astype(np.float32)
    if seed is not None:
        rng = np.random.default_rng(seed)
        m = m * rng.uniform(0.5, 1.0, size=m.shape).astype(np.float32)
        m = m * rng.choice(np.float32([-1.0, 1.0]), size=m.shape)
    return m
