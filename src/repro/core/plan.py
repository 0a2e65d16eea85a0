"""Plan/execute split for the SpAMM pipeline.

The paper's pipeline has two phases with very different reuse behavior:

  * a cheap **gating** phase — get-norm (§3.2) → bitmap → `map_offset`
    compaction (§3.3) — that depends only on the operands' normmaps and τ;
  * an expensive **multiplication** phase (Alg. 2/3) that consumes the
    gating artifacts and the operand data.

For serving-style workloads the right-hand operand (a weight matrix) is
static across requests, so its half of the gating phase can be planned once
and reused for every token batch — the "preprocess once, multiply many"
structure Acc-SpMM and tSparse use to make sparse tensor-core kernels pay
off. This module is the ONE implementation of the gating phase (mask,
super-column grouping, compaction); every other call site
(`kernels.ops.spamm_matmul`, `core.spamm.spamm`, `core.module.spamm_linear`,
`core.distributed.spamm_rowpart/_2d`) builds a `SpammPlan` here and runs it
through `execute`.

Hierarchical (norm-pyramid) gating: the original SpAMM is a *recursive*
algorithm; the flat one-level gate re-derived here costs O(gm·gn·gk) norm
products regardless of sparsity. Since a coarse tile's Frobenius norm
upper-bounds every sub-tile's norm, a coarse-level τ-test that fails rules
out every fine pair inside it — so a `NormPyramid` (levels of sqrt-sumsq
pooled normmaps) gives *exact* coarse-to-fine pruning: `plan(..., levels=L)`
gates at the coarsest level first and refines only inside surviving coarse
blocks, producing a mask bit-identical to flat gating while plan
construction becomes sub-linear in the pruned region.

Compacted execution (§3.3 map_offset, kept first-class end to end): for
concrete operands the planner never round-trips through a dense bitmap — the
hierarchical descent (or the flat gate's nonzero scan) yields the surviving
(i, j, k) triples directly, and `compact_from_triples` turns them into a
`SpammWork` work-list (per-(i, j) row/col ids, concatenated ascending
k-lists with offsets, and bucket-padded per-step tables) in O(V log V) of
the V SURVIVING triples — no O(gm·gn·gk log gk) sort over the grid. The
Pallas backends execute the work-list on a 1-D grid of Σnvalid steps
(`kernels.spamm_mm.spamm_mm_worklist`); the dense mask becomes a lazy
derived view, materialized only for backends that gate from the bitmap
(jnp masked einsum) or for traced plans, where shapes must be static and
the legacy dense-kidx path (`spamm_compact_ref`) still applies.

API:
  plan(a, b, tau | valid_ratio=...)  → SpammPlan   (or from precomputed
                                       normmaps via norm_a= / norm_b=;
                                       levels=L turns on pyramid gating)
  execute(plan, a, b)                → C
  SpammWork / compact_from_triples   — flattened work-list straight from
                                       the descent's surviving triples
  NormPyramid                        — coarse-to-fine normmap stack
  hier_gate_mask(pyr_a, pyr_b, tau)  — coarse-to-fine mask (≡ gate_mask)
  WeightPlanCache                    — per-weight gating artifacts, keyed on
                                       weight identity/shape/tile/levels
  spamm_bmm(x, w, tau)               — batched (B,M,K)@(K,N) / (B,K,N) with
                                       the weight-side plan shared across
                                       the batch
"""
from __future__ import annotations

import collections
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost as kcost
from repro.kernels import ops as kops
from repro.kernels import quantize as kquant
from repro.kernels import ref as kref
from repro.kernels import spamm_mm as kmm
from repro.obs.tracer import annotate


# ---------------------------------------------------------------------------
# padding helper (shared by every caller that accepts arbitrary shapes)
# ---------------------------------------------------------------------------

def pad_to_tile(x: jax.Array, tile: int, tile_n: Optional[int] = None
                ) -> jax.Array:
    """Zero-pad the trailing two dims of x up to multiples of `tile`.

    tile_n overrides the multiple for the LAST dim — the weight side of a
    block_n > 1 product must pad N to tile·block_n so the super-column
    grouping divides the column grid (`gn % block_n == 0`)."""
    m, n = x.shape[-2:]
    pm, pn = (-m) % tile, (-n) % (tile_n or tile)
    if pm == 0 and pn == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)]
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# NormPyramid — coarse-to-fine normmap stack
# ---------------------------------------------------------------------------

# Relative slack applied to τ at coarse levels only: coarse norms are computed
# in fp32 (sqrt of pooled sumsq), so a coarse product can round a hair below a
# fine product it mathematically dominates. The slack widens the candidate set
# (never prunes extra), keeping the level-0 test — which is exactly the flat
# gate — the sole decider of the final mask. Bit-identity to flat gating is
# therefore unconditional; 1e-5 covers the fp32 rounding of several pooling
# levels with orders of magnitude to spare.
_COARSE_SLACK = 1e-5


@jax.tree_util.register_pytree_node_class
class NormPyramid:
    """Coarse-to-fine stack of normmaps for one operand side.

    levels[0] is the plain normmap at `tile`; levels[l] ceil-halves each grid
    dim of levels[l-1] by sqrt-of-sumsq pooling, so levels[l][I, J] is the
    exact Frobenius norm of the (tile·2^l)² block (zero-padded at ragged
    edges) and upper-bounds every descendant tile norm. Built from ONE
    get-norm pass over the matrix plus `num_levels` cheap reductions.

    A pytree (children = the level arrays), so pyramids pass through
    jit/vmap and live in caches exactly like plain normmaps.
    """

    def __init__(self, levels, *, tile: int):
        self.levels = tuple(levels)
        self.tile = tile

    def tree_flatten(self):
        return self.levels, (self.tile,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children, tile=aux[0])

    @property
    def base(self) -> jax.Array:
        """The finest normmap — what flat gating / SpammPlan.norm_* store."""
        return self.levels[0]

    @property
    def coarse(self) -> jax.Array:
        return self.levels[-1]

    @property
    def num_levels(self) -> int:
        """Number of coarsening steps (0 ⇒ just the flat normmap)."""
        return len(self.levels) - 1

    @property
    def coarse_tile(self) -> int:
        return self.tile * (2 ** self.num_levels)

    def extended(self, levels: int) -> "NormPyramid":
        """This pyramid deepened to `levels` coarsening steps (no-op if
        already at least that deep) — pools from the current coarsest."""
        if self.num_levels >= levels:
            return self
        lv = list(self.levels)
        for _ in range(levels - self.num_levels):
            lv.append(kref.pool_norms_ref(lv[-1]))
        return NormPyramid(lv, tile=self.tile)

    @classmethod
    def from_normmap(cls, normmap: jax.Array, levels: int, *, tile: int = 64
                     ) -> "NormPyramid":
        """Pyramid from an existing finest normmap (reuses the get-norm pass
        that produced it; each level is one pooling reduction)."""
        lv = [normmap]
        for _ in range(levels):
            lv.append(kref.pool_norms_ref(lv[-1]))
        return cls(lv, tile=tile)

    @classmethod
    def build(cls, x: jax.Array, levels: int, *, tile: int = 64,
              backend: str = "auto", use_mxu: bool = False) -> "NormPyramid":
        """Pyramid from the matrix via the backend's pyramid_norms kernel."""
        return cls(
            kops.pyramid_norms(x, tile, levels, backend=backend,
                               use_mxu=use_mxu),
            tile=tile,
        )


# ---------------------------------------------------------------------------
# compacted work-list (§3.3 map_offset, straight from the descent)
# ---------------------------------------------------------------------------

# per-step flag bits of the ragged kernel — the kernel module owns them so
# encoder (here) and decoder (kernel body) can never disagree.
STEP_INIT = kmm.STEP_INIT
STEP_ACC = kmm.STEP_ACC
STEP_FLUSH = kmm.STEP_FLUSH


class SpammWork(NamedTuple):
    """Flattened per-(i, j) work-list of one plan — the compacted form of
    the §3.3 map_offset, kept instead of (not re-derived from) the bitmap.

    Pair view (what `info()`/tests consume):
      rows     (P,)   int32 — row-tile id of each active output pair
      cols     (P,)   int32 — super-column id (block_n granularity)
      offsets  (P+1,) int32 — klist[offsets[p]:offsets[p+1]] is pair p's
                              ascending valid-k list
      klist    (V,)   int32 — concatenated valid k's; V = Σnvalid

    Step view (what drives `spamm_mm_worklist`'s 1-D grid; built once here
    so repeated `execute` calls pay nothing — None on plans for backends
    with no ragged executor, which keep an eager bitmap/kidx instead):
      step_i/step_j/step_k  (S,) int32 — per-grid-step block ids, S = V
                            padded to a bucket (padding repeats the last
                            real triple so Pallas revisits, no re-fetch)
      step_flags            (S,) int32 — STEP_INIT/ACC/FLUSH bits; padding
                            steps carry no bits (no accumulate, no flush)

    A NamedTuple of arrays, hence a pytree: plans carrying work pass
    through jit (shapes are static per plan instance).
    """
    rows: jax.Array
    cols: jax.Array
    offsets: jax.Array
    klist: jax.Array
    step_i: jax.Array
    step_j: jax.Array
    step_k: jax.Array
    step_flags: jax.Array

    @property
    def num_pairs(self) -> int:
        return self.rows.shape[0]

    @property
    def num_valid(self) -> int:
        return self.klist.shape[0]


# the ONE bucket function lives in core.cost (the autotuner searches over
# its `minimum`); these aliases keep the historical import path working —
# `bucket_ladder` is the compile-count bound shape-bucketed serving asserts
_bucket = kcost.bucket
bucket_ladder = kcost.bucket_ladder


def compact_from_triples(ii, jj, kk, *, gm: int, gn: int, gk: int,
                         block_n: int = 1, steps: bool = True,
                         assume_sorted: bool = False, bucket_min: int = 16):
    """kidx/nvalid straight from surviving (i, j, k) triples — §3.3
    map_offset compaction WITHOUT materializing or sorting the dense
    (gm, gn, gk) bitmap.

    ii/jj/kk: integer arrays of the surviving triples in any order (the
    hierarchical descent's output, or the flat gate's nonzero scan), with
    jj at FINE column granularity; duplicates after super-column grouping
    are folded. Cost is O(V log V) in the V surviving triples (one fused-key
    argsort + linear passes) — sub-linear in the grid for pruned products,
    vs the legacy `spamm_compact_ref` sort over all gm·gn·gk slots.

    Returns (work: SpammWork of numpy arrays, nvalid: (gm, gn//block_n)
    int32 numpy) — nvalid is the paper's validNum, scattered from the
    work-list (a cheap (gm, gnb) array, NOT the dense bitmap).

    steps=False skips the bucket-padded per-step tables (their fields come
    back None): backends with no ragged executor never read them, so the
    planner saves their construction and device upload on, e.g., the jnp
    serving hot path while the pair view still powers `info()`.

    assume_sorted=True skips the O(V log V) sort for callers whose triples
    already arrive in ascending fused-key, i.e. (i, j, k) row-major, order
    and without duplicates — the flat gate's chunked nonzero scan is one
    (making the flat eager path O(V)); the hierarchical descent is not.

    bucket_min is the power-of-two bucket floor of the per-step tables
    (`core.cost.bucket(v, bucket_min)`): the autotuner raises it per weight
    to cut jit recompiles when successive calls straddle bucket boundaries.
    """
    assert gn % block_n == 0, (gn, block_n)
    gnb = gn // block_n
    ii = np.asarray(ii, np.int64).ravel()
    kk = np.asarray(kk, np.int64).ravel()
    jb = np.asarray(jj, np.int64).ravel()
    if block_n > 1:
        jb = jb // block_n
    # one fused-key sort instead of a 3-key lexsort (~2× on the hot path);
    # int64 keys cannot overflow for any grid whose bitmap would fit memory
    key = (ii * gnb + jb) * gk + kk
    if not assume_sorted:
        key = np.sort(key)
    if block_n > 1 and key.size:
        # member columns of one super-column collapse to the same (i, jb, k)
        keep = np.ones(key.size, bool)
        keep[1:] = key[1:] != key[:-1]
        key = key[keep]
    kk = (key % gk).astype(np.int32)
    pair = key // gk
    jb = (pair % gnb).astype(np.int32)
    ii = (pair // gnb).astype(np.int32)
    v = ii.size
    nvalid = np.zeros((gm, gnb), np.int32)
    step_i = step_j = step_k = step_flags = None
    if steps:
        s = _bucket(v, bucket_min)
        step_i = np.zeros(s, np.int32)
        step_j = np.zeros(s, np.int32)
        step_k = np.zeros(s, np.int32)
        step_flags = np.zeros(s, np.int32)
    if v:
        newpair = np.ones(v, bool)
        newpair[1:] = pair[1:] != pair[:-1]
        starts = np.flatnonzero(newpair).astype(np.int32)
        rows, cols = ii[starts], jb[starts]
        offsets = np.append(starts, np.int32(v)).astype(np.int32)
        nvalid[rows, cols] = np.diff(offsets)
        if steps:
            step_i[:v], step_j[:v], step_k[:v] = ii, jb, kk
            step_i[v:], step_j[v:], step_k[v:] = ii[-1], jb[-1], kk[-1]
            flags = np.full(v, STEP_ACC, np.int32)
            flags[starts] |= STEP_INIT
            flags[np.append(starts[1:], v) - 1] |= STEP_FLUSH
            step_flags[:v] = flags
    else:
        rows = cols = np.zeros(0, np.int32)
        offsets = np.zeros(1, np.int32)
        if steps:
            # no real steps: every grid step maps to output block (0, 0) and
            # on real TPU its VMEM window is copied back at window end even
            # if the kernel never stores — make step 0 init+flush the (zero)
            # accumulator so that block is written with zeros, not garbage
            step_flags[0] = STEP_INIT | STEP_FLUSH
    work = SpammWork(rows=rows, cols=cols, offsets=offsets, klist=kk,
                     step_i=step_i, step_j=step_j, step_k=step_k,
                     step_flags=step_flags)
    return work, nvalid


def kidx_from_work(work: SpammWork, gm: int, gnb: int, gk: int) -> np.ndarray:
    """Dense (gm, gnb, gk) kidx table from a work-list — same layout as
    `spamm_compact_ref` (ascending valid k's first, padding slots repeat the
    last valid k, all-invalid pairs read 0) but built by O(V) scatters, no
    sort over the grid. Only needed for backends whose dense-grid kernel
    consumes kidx but lack a `matmul_worklist` entry point."""
    rows = np.asarray(work.rows)
    cols = np.asarray(work.cols)
    offsets = np.asarray(work.offsets)
    klist = np.asarray(work.klist)
    lastk = np.zeros((gm, gnb), np.int32)
    if klist.size:
        lastk[rows, cols] = klist[offsets[1:] - 1]
    kidx = np.broadcast_to(lastk[:, :, None], (gm, gnb, gk)).copy()
    if klist.size:
        counts = np.diff(offsets)
        t = np.arange(klist.size, dtype=np.int32) - np.repeat(
            offsets[:-1], counts)
        kidx[np.repeat(rows, counts), np.repeat(cols, counts), t] = klist
    return kidx


# ---------------------------------------------------------------------------
# SpammPlan
# ---------------------------------------------------------------------------

class SpammInfo(NamedTuple):
    tau: jax.Array              # threshold actually used
    valid_fraction: jax.Array   # executed-tile fraction (== paper valid ratio)
    effective_flops: jax.Array  # 2·M·K·N · valid_fraction


@jax.tree_util.register_pytree_node_class
class SpammPlan:
    """Cached gating phase of one SpAMM product.

    Array fields (pytree children — a plan passes through jit/vmap):
      tau         f32 scalar
      norm_a      (gm, gk)  A-side normmap
      norm_b      (gk, gn)  B-side normmap
      mask        (gm, gn//block_n, gk) bool — validity bitmap at
                  super-column granularity (block_n=1 ⇒ per-tile). LAZY for
                  work-list plans: stored as None and scattered from `work`
                  only if a caller actually reads it (the ragged executor
                  never does).
      kidx        (gm, gn//block_n, gk) int32 compacted valid-k lists, or
                  None when the backend gates from `mask` directly or
                  executes the work-list
      nvalid      (gm, gn//block_n) int32, or None (as above)
      valid_tiles i32 scalar — Σnvalid (== Σ mask)
      work        SpammWork or None — the §3.3 compacted work-list, present
                  on every concretely-planned product; `execute` drives the
                  ragged kernel from it when the backend has one.
      a_scale     (gm, gk) f32 per-tile int8 scales for A, or None — present
                  only on int8 plans built from the matrix; `execute`
                  recomputes missing scales (quantization is a pure function
                  of the operand, so either way is bit-identical).
      b_scale     (gk, gn) f32 per-FINE-tile int8 scales for B, or None.

    Static metadata (aux): tile, block_n, backend (resolved name), levels
    (pyramid coarsening steps the mask was gated with; 0 = flat — the mask is
    bit-identical either way, `levels` only records how it was built), and
    compute_dtype ("float32" | "bfloat16" | "int8" — the precision `execute`
    feeds the kernel; the plan's τ is already quantization-widened and its
    normmaps describe the quantized operand view, see kernels/quantize.py).
    """

    def __init__(self, tau, norm_a, norm_b, mask, kidx, nvalid, valid_tiles,
                 work=None, a_scale=None, b_scale=None, *, tile: int,
                 block_n: int, backend: str, levels: int = 0,
                 compute_dtype: str = "float32"):
        self.tau = tau
        self.norm_a = norm_a
        self.norm_b = norm_b
        self._mask = mask
        self.kidx = kidx
        self.nvalid = nvalid
        self.valid_tiles = valid_tiles
        self.work = work
        self.a_scale = a_scale
        self.b_scale = b_scale
        self.tile = tile
        self.block_n = block_n
        self.backend = backend
        self.levels = levels
        self.compute_dtype = compute_dtype

    # -- pytree protocol ----------------------------------------------------
    @property
    def _mask_is_derived(self) -> bool:
        """True when the mask is a lazy view over the step tables (ragged-
        executor plans); such plans keep the executable truth in `work`."""
        return self.work is not None and self.work.step_i is not None

    def tree_flatten(self):
        # plans whose mask is a derived cache of the step tables flatten it
        # as None unconditionally: including it once materialized would
        # change the treedef (None leaf → array leaf), silently invalidating
        # jit caches keyed on the plan structure. Mask-primary plans always
        # flatten the real bitmap.
        mask_child = None if self._mask_is_derived else self._mask
        children = (self.tau, self.norm_a, self.norm_b, mask_child,
                    self.kidx, self.nvalid, self.valid_tiles, self.work,
                    self.a_scale, self.b_scale)
        return children, (self.tile, self.block_n, self.backend, self.levels,
                          self.compute_dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        tile, block_n, backend, levels, compute_dtype = aux
        return cls(*children, tile=tile, block_n=block_n, backend=backend,
                   levels=levels, compute_dtype=compute_dtype)

    # -- derived quantities -------------------------------------------------
    @property
    def grid(self):
        """(gm, gn//block_n, gk) — from the normmaps, so reading it never
        forces the lazy mask."""
        gm, gk = self.norm_a.shape
        gn = self.norm_b.shape[-1]
        return gm, gn // self.block_n, gk

    @property
    def mask(self) -> jax.Array:
        """The dense validity bitmap — a derived view for work-list plans,
        scattered on first read (jnp masked einsum, tests, V-matrix
        consumers); the compacted `work` is the primary representation.

        Scatters from the STEP view, not the pair view: step tables have
        static shapes, so the build traces under jit (a plan re-entering
        through tree_unflatten carries tracer work arrays), whereas the pair
        view needs dynamic-count repeats. plan()'s eager host scatter (for
        backends built WITHOUT step tables) is the numpy twin of this — a
        change to the work-list encoding must update both.
        """
        if self._mask is None:
            gm, gnb, gk = self.grid
            w = self.work
            real = (w.step_flags & STEP_ACC) != 0
            self._mask = (
                jnp.zeros((gm, gnb, gk), bool)
                .at[w.step_i, w.step_j, w.step_k].max(real)
            )
        return self._mask

    @property
    def total_tiles(self) -> int:
        gm, gnb, gk = self.grid
        return gm * gnb * gk

    @property
    def valid_fraction(self) -> jax.Array:
        return self.valid_tiles / self.total_tiles

    def bytes_moved(self):
        """Analytic GEMM bytes the executed work-list moves at this plan's
        compute dtype: per real step one (tile, tile) A block and one
        (tile, tile·block_n) B block at `compute_dtype` itemsize, plus one
        f32 (tile, tile·block_n) output flush per active output pair. The
        mixed-precision bandwidth lever in one number (ROADMAP: cut decode
        GEMM bytes ~2× on the same work-list); int8 scale tables are a few
        f32 scalars per step and are not counted. Delegates to
        `core.cost.gemm_bytes` — the cost model's GEMM-byte term IS this
        formula (pinned by tests/test_cost_model.py), so the autotuner
        prices exactly what the telemetry reports."""
        nvalid = self.nvalid
        if nvalid is not None:
            pairs = jnp.sum(nvalid > 0, dtype=jnp.int32)
        else:
            pairs = jnp.sum(jnp.any(self.mask, axis=-1), dtype=jnp.int32)
        # float accumulation: byte counts overflow int32 well before any
        # interesting grid does
        return kcost.gemm_bytes(
            self.valid_tiles.astype(jnp.float32), pairs.astype(jnp.float32),
            self.tile, self.block_n, self.compute_dtype)

    def info(self) -> dict:
        """The info dict `kernels.ops.spamm_matmul` has always returned.

        `nvalid` is the per-(i, j) valid-k count (the paper's validNum). The
        compacted copy is reused when the planner built one; traced bitmap
        plans get the same counts summed from the mask.
        """
        nvalid = self.nvalid
        if nvalid is None:
            nvalid = jnp.sum(self.mask, axis=-1, dtype=jnp.int32)
        return {
            "norm_a": self.norm_a,
            "norm_b": self.norm_b,
            "nvalid": nvalid,
            "valid_tiles": self.valid_tiles,
            "total_tiles": self.total_tiles,
            "valid_fraction": self.valid_fraction,
        }


# ---------------------------------------------------------------------------
# the gating phase — THE single implementation
# ---------------------------------------------------------------------------

def gate_mask(norm_a: jax.Array, norm_b: jax.Array, tau, block_n: int = 1):
    """Validity bitmap from normmaps (paper Alg. 2 lines 3–8).

    block_n > 1 groups gn into gn//block_n super-columns; a super-column is
    valid for k if ANY of its member columns is (superset mask ⇒ exactness).
    Returns (gm, gn//block_n, gk) bool.
    """
    tau = jnp.asarray(tau, jnp.float32)
    if block_n > 1:
        gk, gn = norm_b.shape
        assert gn % block_n == 0, (gn, block_n)
        nb_g = norm_b.reshape(gk, gn // block_n, block_n)
        fine = norm_a[:, None, :, None] * jnp.swapaxes(nb_g, 0, 1)[None] >= tau
        return jnp.any(fine, axis=-1)
    return kref.spamm_mask_ref(norm_a, norm_b, tau)


# children of one coarse (i, j, k) triple: the 2×2×2 refinement offsets,
# kept as three separate contiguous columns — strided (N, 3) row layout
# costs ~2.5× on the gather-heavy descent below
_OFF_I = np.array([i for i in (0, 1) for _ in (0, 1) for _ in (0, 1)], np.int32)
_OFF_J = np.array([j for _ in (0, 1) for j in (0, 1) for _ in (0, 1)], np.int32)
_OFF_K = np.array([k for _ in (0, 1) for _ in (0, 1) for k in (0, 1)], np.int32)


def _hier_descend_host(la, lb, tau: float):
    """Sparse coarse-to-fine descent on concrete normmaps (numpy) — returns
    the surviving fine (ii, jj, kk) triples DIRECTLY, i.e. already in the
    compacted form `compact_from_triples` consumes (§3.3: the descent owns
    the valid set; scattering it into a bitmap and re-deriving kidx by
    sorting would throw that away).

    la/lb: per-level np normmaps, finest first. Gates the full (tiny)
    coarsest level, then repeatedly expands only the SURVIVING triples into
    their 2×2×2 children — work is O(coarse grid + surviving candidates), not
    O(gm·gn·gk), which is what makes plan construction sub-linear in the
    pruned region. The level-0 test is the exact flat gate, so the triple
    set is exactly the support of `gate_mask`.
    """
    top = len(la) - 1
    tau_c = tau - _COARSE_SLACK * abs(tau)
    na, nb = la[top], lb[top]
    cand = na[:, None, :] * np.swapaxes(nb, 0, 1)[None] >= (tau_c if top else tau)
    ii, jj, kk = [x.astype(np.int32) for x in np.nonzero(cand)]
    for l in range(top - 1, -1, -1):
        gm_l, gk_l = la[l].shape
        gn_l = lb[l].shape[1]
        if ii.shape[0] == 0:
            break
        i2 = (ii[:, None] * 2 + _OFF_I[None]).ravel()
        j2 = (jj[:, None] * 2 + _OFF_J[None]).ravel()
        k2 = (kk[:, None] * 2 + _OFF_K[None]).ravel()
        # ceil-pooled coarse grids overhang ragged fine edges — drop phantoms
        keep = (i2 < gm_l) & (j2 < gn_l) & (k2 < gk_l)
        if not keep.all():
            i2, j2, k2 = i2[keep], j2[keep], k2[keep]
        vals = la[l][i2, k2] * lb[l][k2, j2]
        s = vals >= (tau if l == 0 else tau_c)
        ii, jj, kk = i2[s], j2[s], k2[s]
    return ii, jj, kk


def _hier_mask_host(la, lb, tau: float) -> np.ndarray:
    """Dense bitmap view of `_hier_descend_host` (kept for `hier_gate_mask`
    callers that want the bitmap; the planner consumes the triples)."""
    ii, jj, kk = _hier_descend_host(la, lb, tau)
    gm, gk = la[0].shape
    gn = lb[0].shape[1]
    mask = np.zeros(gm * gn * gk, bool)
    if ii.shape[0]:
        mask[(ii.astype(np.int64) * gn + jj) * gk + kk] = True
    return mask.reshape(gm, gn, gk)


def _hier_mask_traced(la, lb, tau) -> jax.Array:
    """Dense traceable analogue of `_hier_mask_host` for jit'd callers.

    Upsamples the surviving-candidate set level by level and ANDs it with
    each level's gate. No asymptotic saving inside jit (the arrays stay
    dense), but the same exactness argument applies: the candidate set is a
    superset of the flat mask, and the final level applies the exact flat
    test — so cand ∧ flat ≡ flat, bit-identical.
    """
    top = len(la) - 1
    tau = jnp.asarray(tau, jnp.float32)
    tau_c = tau - _COARSE_SLACK * jnp.abs(tau)
    cand = (la[top][:, None, :] * jnp.swapaxes(lb[top], 0, 1)[None]
            >= (tau_c if top else tau))
    for l in range(top - 1, -1, -1):
        gm_l, gk_l = la[l].shape
        gn_l = lb[l].shape[1]
        cand = jnp.repeat(jnp.repeat(jnp.repeat(cand, 2, 0), 2, 1), 2, 2)
        cand = cand[:gm_l, :gn_l, :gk_l]
        t = tau if l == 0 else tau_c
        cand = cand & (la[l][:, None, :] * jnp.swapaxes(lb[l], 0, 1)[None] >= t)
    return cand


def hier_gate_mask(pyr_a: NormPyramid, pyr_b: NormPyramid, tau,
                   block_n: int = 1):
    """Coarse-to-fine validity bitmap — bit-identical to `gate_mask` on the
    finest normmaps (the exactness invariant: a failing coarse product
    upper-bounds, hence rules out, every fine product inside it).

    Concrete operands take the sparse numpy descent (sub-linear in the
    pruned region — the eager planning hot path) and return a HOST (numpy)
    bitmap, letting the planner count valid tiles without an accelerator
    round-trip; traced operands fall back to a dense but jit-compatible
    refinement returning a traced array.
    """
    levels = min(pyr_a.num_levels, pyr_b.num_levels)
    la = list(pyr_a.levels[: levels + 1])
    lb = list(pyr_b.levels[: levels + 1])
    traced = any(isinstance(x, jax.core.Tracer) for x in la + lb + [tau])
    if traced:
        mask = _hier_mask_traced(la, lb, tau)
    else:
        mask = _hier_mask_host(
            [np.asarray(x) for x in la],
            [np.asarray(x) for x in lb],
            float(np.asarray(tau)),
        )
    if block_n > 1:
        gm, gn, gk = mask.shape
        assert gn % block_n == 0, (gn, block_n)
        grouped = mask.reshape(gm, gn // block_n, block_n, gk)
        mask = grouped.any(2) if isinstance(mask, np.ndarray) else \
            jnp.any(grouped, axis=2)
    return mask


def _flat_triples_host(na: np.ndarray, nb: np.ndarray, tau: float,
                       block_n: int, *, keep_mask: bool):
    """Concrete flat gate on host, in row chunks: the fp32 products are
    exactly `gate_mask`'s, but the (gm, gn, gk) float tensor is never held
    whole — each chunk is reduced to bool (and to super-columns) before the
    next is computed, so peak memory is the 1-byte bitmap at most (and only
    when `keep_mask` asks for it, i.e. a dense-path backend will consume it).

    Returns ((ii, jb, kk) super-column-granularity triples, bitmap or None).
    """
    gm, gk = na.shape
    gn = nb.shape[1]
    assert gn % block_n == 0, (gn, block_n)
    gnb = gn // block_n
    nbt = np.ascontiguousarray(nb.T)  # (gn, gk)
    mask = np.zeros((gm, gnb, gk), bool) if keep_mask else None
    # ~64 MB transient fp32 product per chunk
    step = max(1, (1 << 24) // max(gn * gk, 1))
    parts_i, parts_j, parts_k = [], [], []
    for i0 in range(0, gm, step):
        blk = na[i0:i0 + step, None, :] * nbt[None] >= tau
        if block_n > 1:
            blk = blk.reshape(blk.shape[0], gnb, block_n, gk).any(2)
        if keep_mask:
            mask[i0:i0 + step] = blk
        bi, bj, bk_ = np.nonzero(blk)
        parts_i.append((bi.astype(np.int64) + i0))
        parts_j.append(bj)
        parts_k.append(bk_)
    return (np.concatenate(parts_i), np.concatenate(parts_j),
            np.concatenate(parts_k)), mask


def _maybe_compact(mask, backend: str):
    """map_offset compaction (§3.3) when the backend's kernel consumes it."""
    if kops.get_backend(backend).needs_compaction:
        return kref.spamm_compact_ref(mask)
    return None, None


def _any_traced(vals) -> bool:
    """True if any operand (matrix, normmap, pyramid level, or τ) is a
    tracer — i.e. plan() is being called under jit/vmap."""
    for v in vals:
        if isinstance(v, NormPyramid):
            if any(isinstance(l, jax.core.Tracer) for l in v.levels):
                return True
        elif isinstance(v, jax.core.Tracer):
            return True
    return False


def _side_pyramid(norm, x, levels: int, tile: int, bk, use_mxu: bool,
                  side: str) -> NormPyramid:
    """Resolve one operand side (matrix / normmap / pyramid) to a pyramid
    with at least `levels` coarsening steps."""
    if isinstance(norm, NormPyramid):
        return norm.extended(levels)
    if norm is not None:
        return NormPyramid.from_normmap(norm, levels, tile=tile)
    if x is None:
        raise ValueError(f"need `{side}` or `norm_{side}`")
    return NormPyramid(
        kops.pyramid_norms(x, tile, levels, backend=bk.name, use_mxu=use_mxu),
        tile=tile,
    )


def _frozen_step_flags(fp, active: jax.Array) -> jax.Array:
    """Traced INIT/ACC/FLUSH flags over a FrozenPlan's static step tables.

    `active` is the traced per-step activation gate (already AND step_real).
    Pure static-shape cumsum/gather arithmetic: INIT fires on a segment's
    first active step, FLUSH on its last; a segment with NO active step gets
    one forced INIT|FLUSH (no ACC) at its final step so its visited output
    tile is written with explicit zeros — the frozen twin of
    `compact_from_triples`'s empty-plan handling, and bit-identical to the
    eager work-list (same active steps, same ascending-k f32 accumulation).
    """
    act = active.astype(jnp.int32)
    cum = jnp.cumsum(act)
    excl = cum - act                      # actives strictly before each step
    first_excl = excl[fp.seg_first]
    before = excl - first_excl            # actives before, within segment
    total = cum[fp.seg_last] - first_excl  # actives in the whole segment
    init = (active & (before == 0)).astype(jnp.int32)
    flush = (active & (before + 1 == total)).astype(jnp.int32)
    idx = jnp.arange(act.shape[0], dtype=jnp.int32)
    empty_write = ((total == 0) & (idx == fp.seg_last)).astype(jnp.int32)
    return (init * STEP_INIT + act * STEP_ACC + flush * STEP_FLUSH
            + empty_write * (STEP_INIT | STEP_FLUSH))


def _plan_frozen(a, fp, *, norm_a=None, use_mxu_norm: bool = False
                 ) -> SpammPlan:
    """Traced plan from a FrozenPlan weight side: the compiled graph runs
    the activation-side get-norm plus an O(S) gather-compare over the frozen
    step tables — zero weight-side get-norm, zero dense-bitmap sort, and the
    concrete work-list path is the only executed path."""
    from repro.plans.frozen import FrozenPlan, FrozenWeight  # circular-safe

    if isinstance(fp, FrozenWeight):
        if a is None:
            raise ValueError("a FrozenWeight needs the activation to pick "
                             "the row grid; pass `a` or pre-specialize with "
                             "for_rows(gm)")
        if isinstance(fp.nbmax, jax.core.Tracer):
            raise ValueError(
                "FrozenWeight.for_rows must run eagerly (its step tables "
                "are concrete data); specialize before jit and pass the "
                "FrozenPlan as a jit argument")
        fp = fp.for_rows(a.shape[0] // fp.tile)
    assert isinstance(fp, FrozenPlan), type(fp)
    bk = kops.get_backend(fp.backend)
    if bk.needs_compaction and bk.matmul_worklist is None:
        raise ValueError(
            f"backend {bk.name!r} consumes dense kidx tables but has no "
            "work-list entry point — the frozen path cannot feed it; "
            "register a matmul_worklist or use a mask-gating backend")
    tile = fp.tile
    dtype = getattr(fp, "compute_dtype", "float32")
    a_scale = None
    if norm_a is None:
        if a is None:
            raise ValueError("need `a` or `norm_a`")
        # low-precision plans gate on the quantized activation view (the
        # weight-side tables were frozen from the quantized weight, and
        # fp.tau is already the widened gate threshold)
        if dtype == "int8":
            # fused absmax/scale + get-norm: one read of the activation
            # yields the quantized-view norms AND the per-tile scales, so
            # execute() quantizes from plan-carried scales instead of a
            # separate per-call absmax pass
            norm_a, a_scale = kops.int8_norms_and_scales(
                a, tile, backend=bk.name, use_mxu=use_mxu_norm)
        else:
            a_view = (kquant.quantized_view(a, dtype, tile)
                      if dtype != "float32" else a)
            norm_a = bk.norms(a_view, tile, use_mxu=use_mxu_norm)
    gm, gk = norm_a.shape
    if (gm, gk) != (fp.gm, fp.gk):
        raise ValueError(
            f"frozen plan was specialized for a ({fp.gm}, {fp.gk}) "
            f"activation grid, got ({gm}, {gk}) — rebuild with "
            f"for_rows({gm})")
    tau = jnp.asarray(fp.tau, jnp.float32)
    # the traced activation gate: exact flat τ-test per frozen step (the
    # super-column max commutes with the gate — fp32 multiply is monotone
    # in each non-negative factor), restricted to real (non-padding) steps
    pa = norm_a[fp.step_i, fp.step_k]
    pb = fp.nbmax[fp.step_k, fp.step_j]
    active = fp.step_real & (pa * pb >= tau)
    flags = _frozen_step_flags(fp, active)
    work = SpammWork(rows=None, cols=None, offsets=None, klist=None,
                     step_i=fp.step_i, step_j=fp.step_j, step_k=fp.step_k,
                     step_flags=flags)
    nvalid = jnp.zeros((gm, fp.gnb), jnp.int32).at[fp.step_i, fp.step_j].add(
        active.astype(jnp.int32))
    valid_tiles = jnp.sum(active, dtype=jnp.int32)
    return SpammPlan(tau, norm_a, fp.norm_b, None, None, nvalid, valid_tiles,
                     work, a_scale, getattr(fp, "b_scale", None),
                     tile=tile, block_n=fp.block_n, backend=bk.name,
                     levels=fp.num_levels, compute_dtype=dtype)


def plan(
    a: Optional[jax.Array] = None,
    b: Optional[jax.Array] = None,
    tau=None,
    *,
    valid_ratio=None,
    norm_a=None,
    norm_b=None,
    tile: int = 64,
    block_n: int = 1,
    backend: str = "auto",
    use_mxu_norm: bool = False,
    levels: int = 0,
    frozen_weight=None,
    compute_dtype: str = "float32",
    bucket_min: int = 16,
) -> SpammPlan:
    """Build the gating phase for (M, K) @ (K, N), dims divisible by tile
    (and N by tile·block_n) — pad upstream (see `pad_to_tile` /
    `core.spamm.spamm`).

    Either side may be given as the matrix (positional) or as a precomputed
    normmap / NormPyramid (norm_a= / norm_b= keywords; the matrix argument
    may then be omitted). Exactly one of `tau` / `valid_ratio` must be set;
    valid_ratio runs the §3.5.2 τ-search on the normmaps.

    levels > 0 (or a NormPyramid operand) switches to hierarchical gating:
    coarse-to-fine refinement over the norm pyramid. The resulting mask is
    bit-identical to flat gating (levels=0); what changes is the cost of
    building it — sub-linear in the pruned region for concrete operands —
    and a coarse-first τ-search when valid_ratio is given. Under jit
    (traced operands) the plan silently downgrades to flat gating: the mask
    is identical and the sparse descent can't run there, so `levels` is
    free on compiled paths rather than an overhead.

    frozen_weight (a `repro.plans.frozen.FrozenPlan`, or a `FrozenWeight`
    when planning eagerly) replaces the whole weight side with precomputed
    artifacts: τ/tile/block_n/levels/backend/compute_dtype come FROM the
    artifact (the keyword args are ignored), only the activation-side gate
    is computed (pass norm_a= to skip even that), and the resulting plan
    executes via the frozen `SpammWork` step tables — the path compiled
    prefill/decode take with plans as jit inputs.

    compute_dtype ("float32" | "bfloat16" | "int8", aliases accepted) plans
    for low-precision execution: normmaps are computed (in f32) from the
    QUANTIZED operand view — the values the kernel will actually multiply —
    and an explicit τ is widened by the analytic quantization error bound
    (kernels/quantize.py) so the low-precision gate provably keeps every
    tile the f32 gate at the requested τ keeps. With valid_ratio the
    τ-search runs directly on the quantized norms (the target ratio IS the
    spec; no widening on top). Callers who pass precomputed norm_a/norm_b
    at a low dtype are responsible for having computed them from the
    quantized view (`WeightPlanCache.weight_side(dtype=...)` does).

    bucket_min floors the work-list step tables' power-of-two bucket
    (`core.cost.bucket`) — autotuned per weight (`TunedParams.bucket`) so a
    serving stream whose Σnvalid hovers around a bucket boundary stops
    re-jitting; 16 is the historical default.
    """
    # the span opens inside the function, so that the profiler's event of
    # `plan` itself covers it and an idle gap within reads `spamm_plan`
    with annotate("spamm_plan"):
        if frozen_weight is not None:
            if tau is not None or valid_ratio is not None:
                raise ValueError("frozen_weight carries its own tau; pass "
                                 "neither tau nor valid_ratio")
            return _plan_frozen(a, frozen_weight, norm_a=norm_a,
                                use_mxu_norm=use_mxu_norm)
        if (tau is None) == (valid_ratio is None):
            raise ValueError("give exactly one of tau / valid_ratio")
        bk = kops.get_backend(backend)
        bk.check_tile(tile)

        compute_dtype = kquant.canonical_dtype(compute_dtype)
        a_scale = b_scale = None
        if compute_dtype != "float32":
            # gate on what the kernel will multiply. int8: the fused
            # absmax/scale + get-norm kernel turns each operand matrix into
            # (quantized-view norms, per-tile scales) in ONE read — the plan
            # keeps the scales so execute() skips its absmax pass; the matrix
            # slot is cleared because the norms below ARE its only use (the
            # hierarchical path pools pyramids from the fine normmap).
            # bf16: the quantize-dequantized f32 view replaces the operand
            # before any norm computation, as before.
            if compute_dtype == "int8":
                if a is not None and norm_a is None:
                    norm_a, a_scale = kops.int8_norms_and_scales(
                        a, tile, backend=bk.name, use_mxu=use_mxu_norm)
                    a = None
                if b is not None and norm_b is None:
                    norm_b, b_scale = kops.int8_norms_and_scales(
                        b, tile, backend=bk.name, use_mxu=use_mxu_norm)
                    b = None
            else:
                if a is not None:
                    a = kquant.quantized_view(a, compute_dtype, tile)
                if b is not None:
                    b = kquant.quantized_view(b, compute_dtype, tile)
            if tau is not None:
                tau = kquant.widen_tau(tau, compute_dtype, tile)

        hier = (levels > 0 or isinstance(norm_a, NormPyramid)
                or isinstance(norm_b, NormPyramid))
        if hier and _any_traced((a, b, norm_a, norm_b, tau)):
            # Under jit the sparse descent can't run and the dense traced
            # refinement produces the SAME mask as flat gating for strictly more
            # work — downgrade to flat so `levels` is free on compiled paths
            # (jitted prefill) while eager callers keep the hierarchical win.
            # hier_gate_mask stays available for traced callers who want the
            # level-by-level refinement explicitly.
            if isinstance(norm_a, NormPyramid):
                norm_a = norm_a.base
            if isinstance(norm_b, NormPyramid):
                norm_b = norm_b.base
            hier = False
        triples = None          # surviving (i, j, k); j granularity per flag
        triples_grouped = False  # True ⇒ j is already a super-column id
        mask = None
        if hier:
            want = max(
                levels,
                norm_a.num_levels if isinstance(norm_a, NormPyramid) else 0,
                norm_b.num_levels if isinstance(norm_b, NormPyramid) else 0,
            )
            # spamm_norms: from the get-norm dispatch until the norm maps are
            # on the host; τ's search or upload is queued before the pull,
            # so it overlaps the norm kernels
            with annotate("spamm_norms"):
                pyr_a = _side_pyramid(norm_a, a, want, tile, bk, use_mxu_norm,
                                      "a")
                pyr_b = _side_pyramid(norm_b, b, want, tile, bk, use_mxu_norm,
                                      "b")
                norm_a, norm_b = pyr_a.base, pyr_b.base
                if valid_ratio is not None:
                    # circular-safe
                    from repro.core.tau_search import search_tau_pyramid

                    tau, _ = search_tau_pyramid(pyr_a, pyr_b, valid_ratio)
                tau = jnp.asarray(tau, jnp.float32)
                lv = min(pyr_a.num_levels, pyr_b.num_levels)
                host = (None if _any_traced((pyr_a, pyr_b, tau)) else
                        ([np.asarray(x) for x in pyr_a.levels[: lv + 1]],
                         [np.asarray(x) for x in pyr_b.levels[: lv + 1]]))
            if host is None:
                # even with concrete OPERANDS, an enclosing jit turns the
                # nested-jit kernels (pyramid_norms, the τ-search) into tracer
                # producers — the host descent can't run there, so gate with the
                # traced coarse-to-fine refinement (bit-identical mask)
                mask = hier_gate_mask(pyr_a, pyr_b, tau, block_n)
            else:
                # fully concrete: the descent hands over its surviving triples —
                # the compacted set — and no dense bitmap is ever materialized
                triples = _hier_descend_host(*host, float(np.asarray(tau)))
        else:
            with annotate("spamm_norms"):  # as in the hierarchical branch
                if norm_a is None:
                    if a is None:
                        raise ValueError("need `a` or `norm_a`")
                    norm_a = bk.norms(a, tile, use_mxu=use_mxu_norm)
                if norm_b is None:
                    if b is None:
                        raise ValueError("need `b` or `norm_b`")
                    norm_b = bk.norms(b, tile, use_mxu=use_mxu_norm)
                if valid_ratio is not None:
                    from repro.core.tau_search import search_tau  # circular-safe

                    tau, _ = search_tau(norm_a, norm_b, valid_ratio)
                tau = jnp.asarray(tau, jnp.float32)
                host = (None if _any_traced((norm_a, norm_b, tau)) else
                        (np.asarray(norm_a), np.asarray(norm_b)))
            if host is None:
                mask = gate_mask(norm_a, norm_b, tau, block_n)
            else:
                # concrete flat gate on host: same fp32 products as gate_mask,
                # then a nonzero scan — the triples feed compact_from_triples so
                # kidx/nvalid need no sort over the (gm, gn, gk) grid
                triples, mask = _flat_triples_host(
                    *host, float(np.asarray(tau)), block_n,
                    keep_mask=bk.matmul_worklist is None)
                triples_grouped = True

        gm, gk = norm_a.shape
        gn = norm_b.shape[-1]
        gnb = gn // block_n
        if triples is not None:  # concrete plan: compacted-first
            # per-step tables only for backends that will execute the ragged
            # kernel; bitmap/dense-kidx backends never read them
            steps = bk.matmul_worklist is not None
            if triples_grouped:
                # the chunked nonzero scan emits triples in row-major (sorted
                # fused-key) order with grouping already applied — skip the sort
                work_np, nvalid_np = compact_from_triples(
                    *triples, gm=gm, gn=gnb, gk=gk, block_n=1, steps=steps,
                    assume_sorted=True, bucket_min=bucket_min)
            else:
                work_np, nvalid_np = compact_from_triples(
                    *triples, gm=gm, gn=gn, gk=gk, block_n=block_n,
                    steps=steps, bucket_min=bucket_min)
            valid_tiles = jnp.int32(int(work_np.klist.size))
            nvalid = jnp.asarray(nvalid_np)
            # dense kidx only for dense-grid kernels with no ragged entry point
            kidx = (jnp.asarray(kidx_from_work(work_np, gm, gnb, gk))
                    if bk.needs_compaction and bk.matmul_worklist is None
                    else None)
            if mask is None and not steps:
                # no ragged executor means the executable form IS the bitmap (or
                # the kidx above) — scatter it now from the pair view instead of
                # lazily from step tables that were never built (numpy twin of
                # SpammPlan.mask's traceable step-view scatter; keep in sync)
                m_host = np.zeros((gm, gnb, gk), bool)
                counts = np.diff(work_np.offsets)
                m_host[np.repeat(work_np.rows, counts),
                       np.repeat(work_np.cols, counts), work_np.klist] = True
                mask = m_host
            work = SpammWork(*(jnp.asarray(x) if x is not None else None
                               for x in work_np))
            mask = jnp.asarray(mask) if mask is not None else None
        else:  # traced plan: dense bitmap, legacy compaction
            valid_tiles = jnp.sum(mask, dtype=jnp.int32)
            kidx, nvalid = _maybe_compact(mask, bk.name)
            work = None
        return SpammPlan(tau, norm_a, norm_b, mask, kidx, nvalid, valid_tiles,
                         work, a_scale, b_scale, tile=tile, block_n=block_n,
                         backend=bk.name, levels=(want if hier else 0),
                         compute_dtype=compute_dtype)


def execute(p: SpammPlan, a: jax.Array, b: jax.Array, *, out_dtype=None):
    """Run the multiplication phase of a prebuilt plan on (a, b).

    a/b must have the tile-padded shapes the plan was built for. Executing
    the same plan twice on the same operands is bit-identical to the
    unplanned `kernels.ops.spamm_matmul` — the plan IS that call's first
    half.

    Low-precision plans (`p.compute_dtype`): callers keep passing the
    ORIGINAL operands — execute owns the cast/quantization. bf16 casts both
    operands and takes the normal kernel entry points (f32 accumulate is
    their contract); int8 quantizes per tile (reusing plan-stored scales
    when present — bit-identical either way, quantization is a pure function
    of the operand) and drives `matmul_worklist_int8`. Backends without the
    int8 entry point (jnp/third-party) get the widen-to-f32 fallback: the
    dequantized f32 view runs the normal path, numerically the product the
    int8 kernel approximates to a few ulps.
    """
    gm, gk = p.norm_a.shape
    _, gn = p.norm_b.shape
    t = p.tile
    assert a.shape == (gm * t, gk * t), (a.shape, (gm * t, gk * t))
    assert b.shape == (gk * t, gn * t), (b.shape, (gk * t, gn * t))
    bk = kops.get_backend(p.backend)
    dtype = getattr(p, "compute_dtype", "float32")
    if dtype == "int8":
        a_q, a_s = kquant.quantize_tiles(a, t, scales=p.a_scale)
        b_q, b_s = kquant.quantize_tiles(b, t, scales=p.b_scale)
        if (p.work is not None and p.work.step_i is not None
                and bk.matmul_worklist_int8 is not None):
            return bk.matmul_worklist_int8(
                a_q, b_q, a_s, b_s, p.work, p.tile, p.block_n,
                out_dtype or jnp.float32)
        # widen-to-f32 fallback: dequantize and take the normal path
        a = kquant.dequantize_tiles(a_q, a_s, t)
        b = kquant.dequantize_tiles(b_q, b_s, t)
    elif dtype == "bfloat16":
        if p.work is not None and bk.matmul_worklist is not None:
            # the worklist kernel is dtype-blind: bf16 operands feed the
            # MXU's native bf16×bf16→f32 path, accumulator stays f32
            a = a.astype(jnp.bfloat16)
            b = b.astype(jnp.bfloat16)
        else:
            # widen-to-f32 fallback: f32 math over the bf16-rounded values
            a = a.astype(jnp.bfloat16).astype(jnp.float32)
            b = b.astype(jnp.bfloat16).astype(jnp.float32)
    if p.work is not None and bk.matmul_worklist is not None:
        # ragged path: Σnvalid grid steps, dense mask never materialized
        return bk.matmul_worklist(a, b, p.work, p.tile, p.block_n,
                                  out_dtype or jnp.float32)
    return bk.matmul(a, b, p.mask, p.kidx, p.nvalid, p.tile, p.block_n,
                     out_dtype or jnp.float32)


# ---------------------------------------------------------------------------
# per-weight plan cache (serving hot path)
# ---------------------------------------------------------------------------

class _WeightEntry(NamedTuple):
    weight: Any          # strong ref: anchors the id() key (no stale reuse)
    padded: jax.Array
    norms: Any           # normmap (levels=0) or NormPyramid (levels>0)


class WeightPlanCache:
    """Caches the weight-side gating artifacts (tile padding + normmap or
    full norm pyramid), keyed on weight identity/shape/dtype/tile/backend/
    levels.

    Serving engines and eager model forward passes call the same weight
    matrix against a stream of activations; the activation-side normmap and
    the bitmap depend on the batch, but the weight normmap (the expensive
    O(K·N) half of get-norm) and the padded copy do not — compute them once
    per weight instead of per token batch. With levels > 0 the cache holds
    the weight-side NormPyramid, so hierarchical replans pay zero weight-side
    work beyond the first request.

    Tracers are never cached (inside jit the trace itself is cached, and
    tracer ids are meaningless); the cache is an eager-path optimization.
    LRU-bounded; `hits`/`misses` expose effectiveness for tests/benchmarks.

    Frozen tier: `frozen_weight` memoizes `repro.plans.frozen.FrozenWeight`
    artifacts by content fingerprint, falling through to the attached
    `PlanStore` (`self.store`) and only then to a fresh build — the cache is
    the in-memory tier above the on-disk store, so a warm store makes
    engine start-up a pure load (no get-norm pass).
    """

    def __init__(self, maxsize: int = 256, store=None):
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self.store = store           # optional repro.plans.store.PlanStore
        self._frozen: dict = {}
        self.frozen_hits = 0
        self.frozen_misses = 0

    @staticmethod
    def _cacheable(w) -> bool:
        return isinstance(w, (np.ndarray, jax.Array)) and not isinstance(
            w, jax.core.Tracer
        )

    def weight_side(self, w, *, tile: int, backend: str,
                    use_mxu: bool = False, levels: int = 0,
                    block_n: int = 1, dtype: str = "float32"):
        """(padded_weight, weight_norms) for w, cached on identity.

        w may be 2-D (K, N) → normmap (gk, gn), or 3-D batched (B, K, N) —
        the per-expert MoE shape — → normmap (B, gk, gn) from one reshaped
        get-norm pass (row tiles never cross slices after padding).
        levels > 0 returns a NormPyramid instead of the plain normmap (for
        3-D weights the pyramid levels carry the batch dim). block_n > 1
        pads N to tile·block_n so the super-column grouping always divides
        the column grid (the padding is part of the cache key). dtype (a
        compute dtype) computes the norms from the QUANTIZED weight view —
        what a low-precision execute will multiply — and is part of the
        cache key; the returned padded weight stays the original f32 (the
        executor owns the actual cast/quantization)."""
        bk = kops.get_backend(backend)
        dtype = kquant.canonical_dtype(dtype)

        def compute():
            wp = pad_to_tile(jnp.asarray(w), tile, tile * block_n)
            # 3-D (per-expert MoE) weights norm through one reshaped 2-D
            # pass — row tiles never cross slices after padding
            w2 = (wp.reshape(wp.shape[0] * wp.shape[1], wp.shape[2])
                  if wp.ndim == 3 else wp)
            if dtype == "int8":
                # fused absmax/scale + get-norm: quantized-view norms from
                # one read (the scales are dropped here — execute recomputes
                # them bit-identically; the cache stays dtype-agnostic)
                nw, _ = kops.int8_norms_and_scales(
                    w2, tile, backend=bk.name, use_mxu=use_mxu)
            elif dtype != "float32":
                nw = bk.norms(kquant.quantized_view(w2, dtype, tile), tile,
                              use_mxu=use_mxu)
            else:
                nw = bk.norms(w2, tile, use_mxu=use_mxu)
            if wp.ndim == 3:
                nw = nw.reshape(wp.shape[0], wp.shape[1] // tile, -1)
            if levels > 0:
                # batched pooling (pool_norms_ref pools the trailing 2 dims)
                nw = NormPyramid.from_normmap(nw, levels, tile=tile)
            return wp, nw

        if not self._cacheable(w):
            return compute()
        key = (id(w), w.shape, str(w.dtype), tile, bk.name, use_mxu, levels,
               block_n, dtype)
        ent = self._entries.get(key)
        if ent is not None and ent.weight is w:
            self.hits += 1
            self._entries.move_to_end(key)
            return ent.padded, ent.norms
        self.misses += 1
        wp, nw = compute()
        self._entries[key] = _WeightEntry(w, wp, nw)
        if len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
        return wp, nw

    def plan_for(self, x_padded, w, tau=None, *, valid_ratio=None,
                 tile: int = 64, block_n: int = 1, backend: str = "auto",
                 use_mxu_norm: bool = False, levels: int = 0,
                 compute_dtype: str = "float32"):
        """Full plan for x @ w with the weight side served from the cache.
        x_padded must already be tile-padded. Returns (plan, padded_weight).
        levels > 0 plans hierarchically with the cached weight pyramid.
        compute_dtype plans for low-precision execution: the cached weight
        norms come from the quantized weight view and plan() handles the
        activation view + τ widening (the weight-side b_scale is recomputed
        by execute — bit-identical, quantization is pure).
        """
        compute_dtype = kquant.canonical_dtype(compute_dtype)
        wp, nw = self.weight_side(w, tile=tile, backend=backend,
                                  use_mxu=use_mxu_norm, levels=levels,
                                  block_n=block_n, dtype=compute_dtype)
        p = plan(x_padded, None, tau, valid_ratio=valid_ratio, norm_b=nw,
                 tile=tile, block_n=block_n, backend=backend,
                 use_mxu_norm=use_mxu_norm, levels=levels,
                 compute_dtype=compute_dtype)
        return p, wp

    def frozen_weight(self, w, *, tau, tile: int = 64, block_n: int = 1,
                      levels: int = 0, backend: str = "auto",
                      use_mxu: bool = False, store=None,
                      dtype: str = "float32", tuned=None):
        """FrozenWeight for `w` at the given gating config, through the
        memory → store → build tiers. Keyed on the weight's CONTENT
        fingerprint (slices of a stacked parameter hash stably, unlike
        id()), so repeated engine warm-ups and the precompute CLI agree.
        dtype is the compute dtype the artifact is frozen for (quantized
        norms + widened gate τ + int8 scale tables) and part of the key.
        tuned (a `core.cost.TunedParams`) rides the built artifact as
        provenance + bucket floor; it is NOT part of the cache/store key —
        callers passing tuned params pass the tuned block_n/levels here too
        (that's what addresses the artifact). A store hit that predates the
        field gets `tuned` re-attached so the bucket floor still applies."""
        from repro.plans import frozen as _frozen  # circular-safe
        from repro.plans import store as _pstore

        store = store if store is not None else self.store
        h = _pstore.fingerprint(w)
        resolved = kops.resolve_backend(backend)
        dtype = kquant.canonical_dtype(dtype)
        key = (h, float(tau), tile, block_n, levels, resolved, use_mxu,
               dtype)
        hit = self._frozen.get(key)
        if hit is not None:
            self.frozen_hits += 1
            return hit
        self.frozen_misses += 1
        fw = None
        if store is not None:
            fw = store.get(h, tau=tau, tile=tile, block_n=block_n,
                           levels=levels, backend=resolved, use_mxu=use_mxu,
                           dtype=dtype)
            if fw is not None and fw.tuned is None and tuned is not None:
                fw.tuned = tuned
        if fw is None:
            fw = _frozen.FrozenWeight.build(
                w, tau, tile=tile, block_n=block_n, levels=levels,
                backend=resolved, use_mxu=use_mxu, weight_hash=h,
                compute_dtype=dtype, tuned=tuned)
            if store is not None:
                store.put(fw)
        self._frozen[key] = fw
        return fw

    def clear(self):
        self._entries.clear()
        self.hits = self.misses = 0
        self._frozen.clear()
        self.frozen_hits = self.frozen_misses = 0

    def __len__(self):
        return len(self._entries)


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------

def spamm_bmm(
    x: jax.Array,
    w: jax.Array,
    tau=None,
    *,
    valid_ratio=None,
    tile: int = 64,
    block_n: int = 1,
    backend: str = "auto",
    use_mxu_norm: bool = False,
    out_dtype=None,
    cache: Optional[WeightPlanCache] = None,
    levels: int = 0,
):
    """Batched SpAMM: (B, M, K) @ (K, N) or (B, M, K) @ (B, K, N).

    levels > 0 plans the shared-weight case hierarchically (the batch folds
    into the row-tile grid, so it is one big 2-D product); the per-batch-
    weight case keeps flat per-slice gating (its vmapped masks are already
    per-slice small) while still caching the weight-side artifacts.

    Shared-weight case: the batch dim folds into the row-tile grid — the
    whole batch runs as ONE (B·M, K) @ (K, N) product whose row tiles never
    cross slice boundaries, so the gating is exactly the per-slice gating
    while the weight-side plan (normmap + padding, optionally from `cache`)
    is computed once and shared across the batch. Per-batch-weight case:
    normmaps for every slice come from one reshaped get-norm call, gating is
    vmapped, and the multiplication runs per slice under lax.map (jnp
    backend: vmapped masked einsum).

    Arbitrary shapes are zero-padded to tile multiples and un-padded.
    Returns (C (B, M, N), SpammInfo).
    """
    if (tau is None) == (valid_ratio is None):
        raise ValueError("give exactly one of tau / valid_ratio")
    bsz, m, k = x.shape
    bk = kops.get_backend(backend)
    out_dtype = out_dtype or jnp.float32

    if w.ndim == 2:  # (B, M, K) @ (K, N): fold batch into the row-tile grid
        k2, n = w.shape
        assert k == k2, (x.shape, w.shape)
        xp = pad_to_tile(x, tile)
        mp, kp = xp.shape[1:]
        if cache is not None:
            wp, nw = cache.weight_side(w, tile=tile, backend=backend,
                                       use_mxu=use_mxu_norm, levels=levels,
                                       block_n=block_n)
        else:
            wp = pad_to_tile(w, tile, tile * block_n)
            nw = bk.norms(wp, tile, use_mxu=use_mxu_norm)
            if levels > 0:
                nw = NormPyramid.from_normmap(nw, levels, tile=tile)
        x2 = xp.reshape(bsz * mp, kp)
        p = plan(x2, None, tau, valid_ratio=valid_ratio, norm_b=nw,
                 tile=tile, block_n=block_n, backend=backend,
                 use_mxu_norm=use_mxu_norm, levels=levels)
        c = execute(p, x2, wp, out_dtype=out_dtype)
        c = c.reshape(bsz, mp, -1)[:, :m, :n]
        frac = p.valid_fraction
        tau_used = p.tau
    else:  # (B, M, K) @ (B, K, N): per-slice plans, weight norms in one pass
        if valid_ratio is not None:
            raise ValueError("valid_ratio needs a shared weight; pass tau for "
                             "per-batch weights")
        assert w.shape[0] == bsz and w.shape[1] == k, (x.shape, w.shape)
        n = w.shape[2]
        xp = pad_to_tile(x, tile)
        mp, kp = xp.shape[1:]
        gm, gk = mp // tile, kp // tile
        if cache is not None:
            wp, nw = cache.weight_side(w, tile=tile, backend=backend,
                                       use_mxu=use_mxu_norm, block_n=block_n)
        else:
            wp = pad_to_tile(w, tile, tile * block_n)
            np_ = wp.shape[2]
            nw = bk.norms(wp.reshape(bsz * kp, np_), tile,
                          use_mxu=use_mxu_norm).reshape(bsz, gk, -1)
        na = bk.norms(xp.reshape(bsz * mp, kp), tile,
                      use_mxu=use_mxu_norm).reshape(bsz, gm, gk)
        tau_used = jnp.asarray(tau, jnp.float32)
        mask = jax.vmap(lambda a_, b_: gate_mask(a_, b_, tau_used, block_n))(
            na, nw)
        if bk.needs_compaction:
            kidx, nvalid = jax.vmap(kref.spamm_compact_ref)(mask)
            c = jax.lax.map(
                lambda s: bk.matmul(s[0], s[1], s[2], s[3], s[4], tile,
                                    block_n, out_dtype),
                (xp, wp, mask, kidx, nvalid),
            )
        else:
            c = jax.vmap(
                lambda a_, b_, m_: bk.matmul(a_, b_, m_, None, None, tile,
                                             block_n, out_dtype)
            )(xp, wp, mask)
        c = c[:, :m, :n]
        frac = jnp.sum(mask, dtype=jnp.int32) / mask.size

    return c, SpammInfo(
        tau=jnp.asarray(tau_used, jnp.float32),
        valid_fraction=frac,
        effective_flops=frac * (2.0 * bsz * m * k * n),
    )
