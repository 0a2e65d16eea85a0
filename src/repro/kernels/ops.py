"""Backend registry + jit'd wrappers for the cuSpAMM kernels.

backends (each a `Backend` record in `BACKENDS`):
  "pallas"    — compiled Pallas TPU kernels (requires a real TPU; tiles
                must be multiples of 128, `repro.kernels.common`).
  "interpret" — Pallas kernels executed with interpret=True (CPU-correctness
                path; runs the exact kernel body in Python/XLA emulation).
  "jnp"       — pure-jnp oracles from ref.py (used for the CPU dry-run and as
                the differentiable path inside models).
  "auto"      — "pallas" when a TPU is attached, else "jnp".

A `Backend` bundles the two kernel entry points the SpAMM pipeline needs:
`norms` (the §3.2 get-norm kernel) and `matmul` (the §3.3 multiplication
kernel, driven by a prebuilt `repro.core.plan.SpammPlan`'s mask/compaction).
Both `tile_norms` and the plan executor (`repro.core.plan.execute`) dispatch
through this one table — adding a backend means registering one record, not
editing every call site.

The mask/compaction/gating logic itself lives in exactly one place:
`repro.core.plan`. `spamm_matmul` below is a thin plan-then-execute
convenience wrapper kept for the one-shot (unplanned) call shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import common as _common
from repro.kernels import getnorm as _getnorm
from repro.kernels import ref as _ref
from repro.kernels import spamm_mm as _spamm_mm


@functools.cache
def _has_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    """One SpAMM execution backend.

    norms(x, tile, use_mxu)                        → (M//tile, K//tile) f32
    matmul(a, b, mask, kidx, nvalid, tile,
           block_n, out_dtype)                     → (M, N) out_dtype
      `mask` is (gm, gn//block_n, gk) bool; `kidx`/`nvalid` the compacted
      valid-k lists at the same granularity (None when needs_compaction is
      False — the executor then gates from `mask` directly).
    needs_compaction: whether `matmul` consumes kidx/nvalid (the Pallas
      kernels do; the jnp masked-einsum oracle does not, so planners skip
      the compaction sort for it).
    pyramid_norms(x, tile, levels, use_mxu)        → tuple of `levels + 1`
      normmaps, finest first — one get-norm pass + `levels` sqrt-sumsq
      pooling reductions (the norm pyramid of hierarchical gating). None
      ⇒ the planner falls back to norms() + the jnp pooling oracle, so
      third-party backends registered before this entry point keep working.
    matmul_worklist(a, b, work, tile, block_n,
                    out_dtype, kb)                 → (M, N) out_dtype
      the ragged execution path: `work` is a `repro.core.plan.SpammWork`
      (flattened per-(i, j) work-list with padded per-step tables, each
      step covering `kb` k-tiles) and the grid is the work-list's steps,
      not gm·gn·gk. None ⇒ the executor falls back
      to `matmul` with the dense mask/kidx, so third-party backends keep
      working unchanged. bf16 execution needs NO separate entry point: the
      executor passes bf16 operands straight into `matmul_worklist`/`matmul`
      (f32 accumulate is the kernels' contract regardless of input dtype).
    matmul_worklist_int8(a_q, b_q, a_scale, b_scale,
                         work, tile, block_n, out_dtype) → (M, N) out_dtype
      the int8 tensor-core path: per-tile-quantized int8 operands + f32
      scale tables (kernels/quantize.py), int8×int8→int32 MXU dots
      dequantized into the f32 accumulator. None ⇒ the executor widens to
      f32 (dequantizes and takes the normal path), so `jnp`/third-party
      backends keep working at identical numerics-of-record.
    norms_quant(x, tile, use_mxu) → (norms, scales), both (M//tile, K//tile)
      f32 — the fused int8 absmax/scale + get-norm kernel: norms of the
      QUANTIZED view plus the per-tile quantization scales from one read.
      None ⇒ `int8_norms_and_scales` composes the unfused
      quantize→dequantize→norms path (bit-identical results either way).
    matmul_worklist_experts(a, b, step_i, step_j, step_k, step_b,
                            step_flags, tile, kb, out_dtype)
                                                   → (M, N) out_dtype
      the work-list kernel over an expert layer's routed rows: each row
      tile of `a` reads its expert's weight from the stacked (E·K, N) `b`,
      step s at B's k-block `step_b[s]`.
      None ⇒ `core.module.spamm_experts_frozen` gates and multiplies with
      a masked einsum.
    compiled: the kernels go through the TPU compiler, whose block layout
      rules bind the tile (`check_tile`); interpret/jnp take any tile.
    """
    name: str
    norms: Callable[..., jax.Array]
    matmul: Callable[..., jax.Array]
    needs_compaction: bool = True
    pyramid_norms: Callable[..., tuple] = None
    matmul_worklist: Callable[..., jax.Array] = None
    matmul_worklist_int8: Callable[..., jax.Array] = None
    norms_quant: Callable[..., tuple] = None
    matmul_worklist_experts: Callable[..., jax.Array] = None
    compiled: bool = False

    def check_tile(self, tile: int) -> None:
        """Raise a ValueError if this backend cannot run `tile`."""
        if self.compiled:
            _common.check_tile(tile)


def _jnp_norms(x, tile, use_mxu=False):
    del use_mxu  # the einsum oracle has no MXU path
    return _ref.tile_norms_ref(x, tile)


def _jnp_matmul(a, b, mask, kidx, nvalid, tile, block_n, out_dtype):
    del kidx, nvalid
    m, k = a.shape
    _, n = b.shape
    gm, gk, gn = m // tile, k // tile, n // tile
    mask_full = jnp.repeat(mask, block_n, axis=1) if block_n > 1 else mask
    a4 = a.reshape(gm, tile, gk, tile)
    b4 = b.reshape(gk, tile, gn, tile)
    out = jnp.einsum(
        "ijk,ipks,ksjq->ipjq",
        mask_full.astype(jnp.float32).astype(a.dtype),
        a4,
        b4,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(m, n).astype(out_dtype)


def _pallas_norms(interpret):
    def norms(x, tile, use_mxu=False):
        return _getnorm.tile_norms(x, tile, use_mxu=use_mxu, interpret=interpret)

    return norms


def _pallas_pyramid_norms(interpret):
    def pyramid(x, tile, levels, use_mxu=False):
        return _getnorm.norm_pyramid(
            x, tile, levels, use_mxu=use_mxu, interpret=interpret
        )

    return pyramid


def _pallas_matmul(interpret):
    def matmul(a, b, mask, kidx, nvalid, tile, block_n, out_dtype):
        del mask
        return _spamm_mm.spamm_mm(
            a, b, kidx, nvalid,
            tile=tile, block_n=block_n, out_dtype=out_dtype,
            interpret=interpret,
        )

    return matmul


def _pallas_matmul_worklist(interpret):
    def matmul_worklist(a, b, work, tile, block_n, out_dtype, kb=1):
        return _spamm_mm.spamm_mm_worklist(
            a, b, work.step_i, work.step_j, work.step_k, work.step_flags,
            tile=tile, block_n=block_n, kb=kb, out_dtype=out_dtype,
            interpret=interpret,
        )

    return matmul_worklist


def _pallas_matmul_worklist_experts(interpret):
    def matmul_worklist_experts(a, b, step_i, step_j, step_k, step_b,
                                step_flags, tile, kb, out_dtype):
        return _spamm_mm.spamm_mm_worklist_moe(
            a, b, step_i, step_j, step_k, step_b, step_flags, tile=tile,
            kb=kb, out_dtype=out_dtype, interpret=interpret)

    return matmul_worklist_experts


def _pallas_norms_quant(interpret):
    def norms_quant(x, tile, use_mxu=False):
        return _getnorm.tile_norms_quant(
            x, tile, use_mxu=use_mxu, interpret=interpret)

    return norms_quant


def _pallas_matmul_worklist_int8(interpret):
    def matmul_worklist_int8(a_q, b_q, a_scale, b_scale, work, tile, block_n,
                             out_dtype):
        return _spamm_mm.spamm_mm_worklist_int8(
            a_q, b_q, a_scale, b_scale,
            work.step_i, work.step_j, work.step_k, work.step_flags,
            tile=tile, block_n=block_n, out_dtype=out_dtype,
            interpret=interpret,
        )

    return matmul_worklist_int8


BACKENDS = {
    # jnp leaves pyramid_norms unset: the norms() + pool_norms_ref fallback
    # in pyramid_norms() below IS the jnp implementation (one copy to
    # maintain); the Pallas backends register the pooling kernel. It also
    # leaves matmul_worklist unset — the masked einsum already only pays for
    # a dense einsum, and the executor's None-fallback IS the jnp path.
    "jnp": Backend("jnp", _jnp_norms, _jnp_matmul, needs_compaction=False),
    "interpret": Backend("interpret", _pallas_norms(True), _pallas_matmul(True),
                         pyramid_norms=_pallas_pyramid_norms(True),
                         matmul_worklist=_pallas_matmul_worklist(True),
                         matmul_worklist_int8=_pallas_matmul_worklist_int8(True),
                         norms_quant=_pallas_norms_quant(True),
                         matmul_worklist_experts=(
                             _pallas_matmul_worklist_experts(True))),
    "pallas": Backend("pallas", _pallas_norms(False), _pallas_matmul(False),
                      pyramid_norms=_pallas_pyramid_norms(False),
                      matmul_worklist=_pallas_matmul_worklist(False),
                      matmul_worklist_int8=_pallas_matmul_worklist_int8(False),
                      norms_quant=_pallas_norms_quant(False),
                      matmul_worklist_experts=(
                          _pallas_matmul_worklist_experts(False)),
                      compiled=True),
}

VALID_BACKENDS = ("auto", *BACKENDS)


def register_backend(backend: Backend):
    """Extension hook: make a new backend visible to the whole pipeline."""
    BACKENDS[backend.name] = backend


def get_backend(backend: str) -> Backend:
    """Resolve a backend name ("auto" included) to its registry record."""
    if backend == "auto":
        backend = "pallas" if _has_tpu() else "jnp"
    try:
        return BACKENDS[backend]
    except KeyError:
        raise ValueError(f"backend {backend!r} not in {VALID_BACKENDS}") from None


def resolve_backend(backend: str) -> str:
    """Canonical backend name (kept for callers that key on the string)."""
    return get_backend(backend).name


# ---------------------------------------------------------------------------
# kernel wrappers
# ---------------------------------------------------------------------------

def tile_norms(
    x: jax.Array, tile: int = 64, *, backend: str = "auto", use_mxu: bool = False
) -> jax.Array:
    """normmap of x — paper get-norm kernel (§3.2), registry-dispatched."""
    return get_backend(backend).norms(x, tile, use_mxu=use_mxu)


def pyramid_norms(
    x: jax.Array,
    tile: int = 64,
    levels: int = 1,
    *,
    backend: str = "auto",
    use_mxu: bool = False,
) -> tuple:
    """Norm pyramid of x: `levels + 1` normmaps, finest (tile) first, each
    coarser level a sqrt-sumsq 2×2 pooling of the previous (so level l is the
    exact normmap at tile·2^l). Registry-dispatched; backends without a
    pyramid entry point fall back to norms() + the jnp pooling oracle."""
    bk = get_backend(backend)
    if bk.pyramid_norms is not None:
        return bk.pyramid_norms(x, tile, levels, use_mxu=use_mxu)
    maps = [bk.norms(x, tile, use_mxu=use_mxu)]
    for _ in range(levels):
        maps.append(_ref.pool_norms_ref(maps[-1]))
    return tuple(maps)


def int8_norms_and_scales(
    x: jax.Array, tile: int = 64, *, backend: str = "auto",
    use_mxu: bool = False
):
    """(norms, scales) of the int8-quantized view of x — THE entry point
    every int8 planner goes through. Backends with the fused kernel
    (`norms_quant`) pay ONE read of x; others compose the unfused
    quantize → dequantize → norms path. Results are bit-identical either
    way (the int8 codes are exactly representable in f32 and both paths
    share the reduction body), which is what keeps frozen ≡ eager parity
    independent of which backend planned."""
    bk = get_backend(backend)
    if bk.norms_quant is not None:
        return bk.norms_quant(x, tile, use_mxu=use_mxu)
    from repro.kernels import quantize as _quant  # local: keep import light

    q, s = _quant.quantize_tiles(x, tile)
    dq = _quant.dequantize_tiles(q, s, tile)
    return bk.norms(dq, tile, use_mxu=use_mxu), s


def spamm_compact(mask: jax.Array):
    """Compacted valid-k lists from a bitmap — paper map_offset (§3.3)."""
    return _ref.spamm_compact_ref(mask)


def spamm_matmul(
    a: jax.Array,
    b: jax.Array,
    tau,
    *,
    tile: int = 64,
    block_n: int = 1,
    backend: str = "auto",
    use_mxu_norm: bool = False,
    out_dtype=None,
):
    """One-shot SpAMM: `plan` + `execute` fused (see repro.core.plan).

    Shapes (M, K) @ (K, N) with all dims divisible by tile (and N by
    tile*block_n). Use repro.core.spamm.spamm for auto-padding + extras; use
    repro.core.plan.plan/execute directly to amortize the gating phase over
    repeated products with the same operands (serving hot path).
    Returns (C, info) where info carries the normmaps, nvalid and the
    executed-tile fraction (== the paper's valid ratio for this product).
    """
    from repro.core import plan as _plan  # circular-safe (plan imports ops)

    p = _plan.plan(
        a, b, tau,
        tile=tile, block_n=block_n, backend=backend, use_mxu_norm=use_mxu_norm,
    )
    c = _plan.execute(p, a, b, out_dtype=out_dtype)
    return c, p.info()


def spamm_effective_flops(m: int, k: int, n: int, valid_fraction) -> jax.Array:
    """FLOPs actually executed by SpAMM = valid_fraction × dense 2·M·K·N."""
    return valid_fraction * (2.0 * m * k * n)
