"""Pallas TPU get-norm kernel (paper §3.2).

Computes the `normmap`: per-(tile × tile) Frobenius norms of a 2-D array.

TPU adaptation of the paper's reduction design:
  * one grid step reduces one whole LoNum×LoNum tile on the VPU (8×128 lanes);
    the paper's shared-memory tree reduction with sequential addressing has no
    TPU analogue because VMEM has no bank conflicts and the VPU reduces a
    resident tile in one shot.
  * the paper's tensor-core reduction (Eq. 3–4: D = 1·X, D' = D·1) is kept as
    an optional MXU path (`use_mxu=True`): two `lax.dot`s against a ones
    block — useful when the tile is large and MXU-aligned.
  * output blocking: each kernel invocation owns one *row* of the normmap,
    held as an (8, grid_k) block revisited across the k grid dimension (8
    sublanes, so the block is legal for the TPU compiler; every sublane holds
    the same row and the wrapper keeps one). The normmap row stays
    VMEM-resident and is flushed to HBM once — the analogue of the paper's
    "thread 0 writes the result back" without a global sync.

Compiled (interpret=False) calls need a tile that is a multiple of 128
(`repro.kernels.common.check_tile`); interpret mode takes any tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quantize as _quant
from repro.kernels.common import LANE, check_tile, mesh_vma

SUBLANES = 8   # rows of an output row block (one f32 vreg is 8 × 128)


def _tile_sumsq(sq, *, use_mxu: bool):
    """Reduce one resident (t, t) f32 tile of squares to a (1, 1) sum — the
    body shared by the plain and fused-quantizing get-norm kernels (one
    reduction implementation ⇒ the fused norms are bit-identical to the
    unfused quantize→dequantize→norms composition)."""
    if use_mxu:
        # Paper Eq. 3–4 on the MXU: row sums, then their total, each a dot
        # against a lane-dense ones block (every output column is the same)
        ones = jnp.ones((sq.shape[0], LANE), jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        rows = jax.lax.dot_general(
            sq, ones, (((1,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32)      # (t, LANE) row sums
        total = jax.lax.dot_general(
            ones, rows, (((0,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32)      # (LANE, LANE) totals
        # a reduction (not a slice) yields the (1, 1) value: Mosaic cannot
        # broadcast a sliced (1, 1) vector over sublanes and lanes at once
        return jnp.sum(total[:1, :1], keepdims=True)
    return jnp.sum(sq, keepdims=True)


def _put_norm(o_ref, j, val):
    """Write the (1, 1) `val` into column j of the resident (8, grid_k) row
    block: a masked full-block select instead of a scalar store, so the
    store stays lane-dense while j is a grid index."""
    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(col == j, val, o_ref[...])


def _getnorm_kernel(x_ref, o_ref, *, use_mxu: bool):
    x = x_ref[...].astype(jnp.float32)
    _put_norm(o_ref, pl.program_id(1),
              jnp.sqrt(_tile_sumsq(x * x, use_mxu=use_mxu)))


def _getnorm_quant_kernel(x_ref, o_ref, s_ref, *, use_mxu: bool):
    """Fused int8 absmax/scale + get-norm: ONE read of the resident tile
    yields both the per-tile quantization scale and the Frobenius norm OF
    the quantized view (what the int8 kernel will actually multiply).

    Bit-identity with the unfused `quantize_tiles` → `dequantize_tiles` →
    `tile_norms` composition: amax/round/clip are order-independent
    elementwise f32 ops, the int8 codes are integers in [-127, 127] (exactly
    representable in f32, so skipping the int8 round-trip changes nothing),
    and the final reduction is the same `_tile_sumsq` body.
    """
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    scale = (jnp.maximum(jnp.max(jnp.abs(x), keepdims=True), _quant._TINY)
             * jnp.float32(_quant._INV127))
    dq = jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    _put_norm(o_ref, j, jnp.sqrt(_tile_sumsq(dq * dq, use_mxu=use_mxu)))
    _put_norm(s_ref, j, scale)


def _pool_kernel(n_ref, o_ref):
    """sqrt-sumsq 2×2 pooling of a whole even-dimensioned normmap.

    Row pairing and column pairing each run as a dot against a 0/1 pooling
    matrix (fine index // 2 == coarse index), so neither needs strided
    sublane or lane slicing. HIGHEST precision keeps the dots at f32."""
    sq = jnp.square(n_ref[...].astype(jnp.float32))     # (2·gmc, 2·gkc)
    gmc, gkc = o_ref.shape
    gm2, gk2 = sq.shape
    rpool = (jax.lax.broadcasted_iota(jnp.int32, (gmc, gm2), 1) // 2
             == jax.lax.broadcasted_iota(jnp.int32, (gmc, gm2), 0))
    cpool = (jax.lax.broadcasted_iota(jnp.int32, (gk2, gkc), 0) // 2
             == jax.lax.broadcasted_iota(jnp.int32, (gk2, gkc), 1))
    hi = jax.lax.Precision.HIGHEST
    rows = jnp.dot(rpool.astype(jnp.float32), sq, precision=hi,
                   preferred_element_type=jnp.float32)  # (gmc, 2·gkc)
    s = jnp.dot(rows, cpool.astype(jnp.float32), precision=hi,
                preferred_element_type=jnp.float32)     # (gmc, gkc)
    o_ref[...] = jnp.sqrt(s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pool_norms(normmap: jax.Array, *, interpret: bool = False) -> jax.Array:
    """One norm-pyramid coarsening step via the Pallas pooling kernel.

    normmap: (gm, gk) f32 level-(l-1) normmap; odd dims are zero-padded.
    Returns (⌈gm/2⌉, ⌈gk/2⌉) f32 — sqrt of 2×2 sumsq pooling, i.e. the exact
    Frobenius norm of each 2×2 tile group (one cheap reduction, no re-read of
    the underlying matrix). A normmap is small (gm·gk floats), so one grid
    step pools the whole of it: whole-array blocks are always legal.
    """
    gm, gk = normmap.shape
    pm, pk = gm % 2, gk % 2
    if pm or pk:
        normmap = jnp.pad(normmap, ((0, pm), (0, pk)))
    gmc, gkc = (gm + pm) // 2, (gk + pk) // 2
    return pl.pallas_call(
        _pool_kernel,
        out_shape=jax.ShapeDtypeStruct((gmc, gkc), jnp.float32,
                                       vma=mesh_vma(normmap)),
        interpret=interpret,
        name="spamm_norm_pool",
    )(normmap)


@functools.partial(
    jax.jit, static_argnames=("tile", "levels", "use_mxu", "interpret")
)
def norm_pyramid(
    x: jax.Array,
    tile: int = 64,
    levels: int = 1,
    *,
    use_mxu: bool = False,
    interpret: bool = False,
):
    """Coarse-to-fine normmap stack: one get-norm pass + `levels` poolings.

    Returns a tuple (finest → coarsest) of `levels + 1` normmaps; entry l is
    the normmap at tile size tile·2^l (grid dims ceil-halved per level).
    """
    maps = [tile_norms(x, tile, use_mxu=use_mxu, interpret=interpret)]
    for _ in range(levels):
        maps.append(pool_norms(maps[-1], interpret=interpret))
    return tuple(maps)


def _norm_rows_call(kernel, x, tile, n_out, interpret, name):
    """Run a get-norm `kernel` over the (tile × tile) grid of x with `n_out`
    (M//tile, K//tile) f32 outputs, each written as (8, K//tile) row blocks."""
    m, k = x.shape
    if m % tile or k % tile:
        raise ValueError(f"shape {x.shape} not divisible by tile {tile}")
    if not interpret:
        check_tile(tile)
    gm, gk = m // tile, k // tile
    rows = pl.BlockSpec((SUBLANES, gk), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(gm, gk),
        in_specs=[pl.BlockSpec((tile, tile), lambda i, j: (i, j))],
        out_specs=[rows] * n_out,
        out_shape=[jax.ShapeDtypeStruct((gm * SUBLANES, gk), jnp.float32,
                                        vma=mesh_vma(x))] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(x)
    return [o.reshape(gm, SUBLANES, gk)[:, 0] for o in outs]


@functools.partial(
    jax.jit, static_argnames=("tile", "use_mxu", "interpret")
)
def tile_norms(
    x: jax.Array,
    tile: int = 64,
    *,
    use_mxu: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Per-tile Frobenius norms via the Pallas get-norm kernel.

    x: (M, K) with M % tile == 0 == K % tile. Returns (M//tile, K//tile) f32.
    """
    kernel = functools.partial(_getnorm_kernel, use_mxu=use_mxu)
    return _norm_rows_call(kernel, x, tile, 1, interpret, "spamm_getnorm")[0]


@functools.partial(
    jax.jit, static_argnames=("tile", "use_mxu", "interpret")
)
def tile_norms_quant(
    x: jax.Array,
    tile: int = 64,
    *,
    use_mxu: bool = False,
    interpret: bool = False,
):
    """Fused int8-quantization get-norm: per-tile Frobenius norms of the
    int8 quantized VIEW of x plus the per-tile scales, from one read.

    x: (M, K) with M % tile == 0 == K % tile. Returns (norms, scales), both
    (M//tile, K//tile) f32. `norms` is bit-identical to
    `tile_norms(dequantize_tiles(*quantize_tiles(x, tile)), tile)` and
    `scales` to `quantize_tiles(x, tile)[1]` — this kernel just collapses
    the three passes (absmax read, quantize/dequantize write+read, norm
    read) into one, which is how `execute()`-bound int8 plans get their
    activation scales without a separate per-call pass.
    """
    kernel = functools.partial(_getnorm_quant_kernel, use_mxu=use_mxu)
    norms, scales = _norm_rows_call(kernel, x, tile, 2, interpret,
                                    "spamm_getnorm_quant")
    return norms, scales
