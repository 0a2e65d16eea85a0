"""Frozen weight-side SpAMM plans — gating artifacts as jit *inputs*.

cuSpAMM's weight-side norm hierarchy is a pure function of the (static)
weight matrix, yet a jitted serving step re-derives it inside every compiled
trace: tracers are never cached, so the `WeightPlanCache` amortization only
helps eager callers. This module freezes the weight half of the gating phase
into two pytrees that compiled prefill/decode consume as *data*:

  * `FrozenWeight` — the shape-independent artifact: the weight-side
    `NormPyramid`, the super-column max-norm table, and the weight-admissible
    (k, j) pair list (tiles whose weight norm can pass the τ-test for SOME
    activation; with τ > 0 a zero-norm weight tile can never pass). This is
    what `PlanStore` serializes and `WeightPlanCache` memoizes.
  * `FrozenPlan` — `FrozenWeight.for_rows(gm)`: the artifact specialized to
    an activation row grid, carrying the `SpammWork`-style step tables
    (pair-major, ascending k, bucket-padded, each step a k-block of `kb`
    k-tiles chosen by the cost model from the weight's admissible pairs)
    plus the per-step segment index tables that let a *traced* activation
    gate compute the INIT/ACC/FLUSH flags with static shapes. Passed as a
    jit argument, it
    makes the concrete work-list path the only executed path: the compiled
    graph contains the activation-side get-norm and an O(S) gather-compare —
    zero weight-side get-norm ops and zero dense-bitmap sorts.

Exactness: the frozen step tables are a *superset* of every reachable mask
(they enumerate all weight-admissible (i, j, k)); the traced activation gate
`norm_a[i,k] · nbmax[k,j] ≥ τ` re-applies the exact flat test per step
(fp32 multiplication is monotone in each non-negative argument, so the
super-column max commutes with the gate), which keeps the frozen path
bit-identical to the eager `plan()+execute()` pipeline.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost as kcost
from repro.core.cost import TunedParams
from repro.core.plan import NormPyramid, _bucket, pad_to_tile
from repro.kernels import ops as kops
from repro.kernels import quantize as kquant

# Bump when the on-disk/for_rows encoding changes incompatibly: PlanStore
# refuses to load artifacts written under a different version (satellite:
# clear error, never silent wrong-plan execution).
# v2: compute-dtype keying + int8 b_scale tables + quantization-widened
#     gate τ — pre-dtype (v1) stores are refused at PlanStore open.
PLAN_FORMAT_VERSION = 2


@jax.tree_util.register_pytree_node_class
class FrozenWeight:
    """Shape-independent frozen gating artifact of ONE gated weight.

    Array fields (pytree children, all concrete):
      tau      f32 scalar — the τ this artifact was frozen at
      levels   tuple of normmaps, finest (tile) first — the weight-side
               NormPyramid stack (levels[0] is the plain normmap)
      nbmax    (gk, gn//block_n) f32 — per super-column max of levels[0]
               (the traced activation gate tests against this table)
      kj_k/kj_j (W,) int32 — weight-admissible (k, j) tile pairs, sorted by
               (j, k) so `for_rows` emits pair-major ascending-k steps
      b_scale  (gk, gn) f32 per-FINE-tile int8 scales of the padded weight,
               or None for float32/bfloat16 artifacts — frozen at build time
               so serving quantizes the weight bit-identically every start

    Static metadata (aux): tile, block_n, levels (coarsening steps),
    backend (resolved name), wshape (true K, N), padded (Kp, Np),
    weight_hash (content fingerprint, "" when unknown), version,
    compute_dtype — the precision this artifact was frozen for: its normmaps
    describe the QUANTIZED weight view and `for_rows` bakes the
    quantization-widened gate τ into the FrozenPlan (tau here stays the
    REQUESTED τ; it is the store-addressing value) — and `tuned`, the
    `core.cost.TunedParams` record when this artifact's blocking parameters
    came from the roofline autotuner (None for hand-configured artifacts).
    tuned is provenance + the work-list bucket floor `for_rows` pads to; it
    is NOT an addressing field — the tuned block_n/levels already address
    the artifact through the ordinary config echo, and legacy stores
    without the field load as tuned=None.
    """

    def __init__(self, tau, levels, nbmax, kj_k, kj_j, b_scale=None, *,
                 tile: int, block_n: int, num_levels: int, backend: str,
                 wshape: Tuple[int, int], padded: Tuple[int, int],
                 use_mxu: bool = False, weight_hash: str = "",
                 version: int = PLAN_FORMAT_VERSION,
                 compute_dtype: str = "float32",
                 tuned: TunedParams | None = None):
        self.tau = tau
        self.levels = tuple(levels)
        self.nbmax = nbmax
        self.kj_k = kj_k
        self.kj_j = kj_j
        self.b_scale = b_scale
        self.tile = tile
        self.block_n = block_n
        self.num_levels = num_levels
        self.backend = backend
        self.wshape = tuple(wshape)
        self.padded = tuple(padded)
        self.use_mxu = use_mxu
        self.weight_hash = weight_hash
        self.version = version
        self.compute_dtype = compute_dtype
        self.tuned = tuned
        self._rows_cache: dict = {}
        self._kb_cache: dict = {}     # width → chosen kb
        self._blocks: dict = {}       # kb → one row tile's steps

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.tau, self.levels, self.nbmax, self.kj_k, self.kj_j,
                    self.b_scale)
        aux = (self.tile, self.block_n, self.num_levels, self.backend,
               self.wshape, self.padded, self.use_mxu, self.weight_hash,
               self.version, self.compute_dtype, self.tuned)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        tau, levels, nbmax, kj_k, kj_j, b_scale = children
        (tile, block_n, num_levels, backend, wshape, padded, use_mxu, wh,
         ver, dtype, tuned) = aux
        return cls(tau, levels, nbmax, kj_k, kj_j, b_scale, tile=tile,
                   block_n=block_n, num_levels=num_levels, backend=backend,
                   wshape=wshape, padded=padded, use_mxu=use_mxu,
                   weight_hash=wh, version=ver, compute_dtype=dtype,
                   tuned=tuned)

    # -- derived ------------------------------------------------------------
    @property
    def pyramid(self) -> NormPyramid:
        return NormPyramid(self.levels, tile=self.tile)

    @property
    def norm_b(self) -> jax.Array:
        return self.levels[0]

    @property
    def grid(self) -> Tuple[int, int]:
        """(gk, gn//block_n) — the weight-side tile grid at super-column
        granularity."""
        return self.nbmax.shape

    @property
    def num_kj(self) -> int:
        """Number of weight-admissible (k, j) pairs (W)."""
        return int(self.kj_k.shape[0])

    @property
    def bucket_floor(self) -> int:
        """The work-list bucket floor `for_rows` pads to — the autotuned
        value when this artifact carries one, else the historical 16."""
        return self.tuned.bucket if self.tuned is not None else 16

    def config_key(self) -> dict:
        """The config echo that (with the weight hash) addresses this
        artifact in a PlanStore — EVERY field that changes the computed
        normmaps or gate must appear here, or a stale artifact would hit."""
        return {
            "tau": float(np.asarray(self.tau)),
            "tile": self.tile,
            "block_n": self.block_n,
            "levels": self.num_levels,
            "backend": self.backend,
            "use_mxu": self.use_mxu,
            "dtype": self.compute_dtype,
        }

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, w, tau, *, tile: int = 64, block_n: int = 1,
              levels: int = 0, backend: str = "auto", use_mxu: bool = False,
              weight_hash: str = "",
              compute_dtype: str = "float32",
              tuned: TunedParams | None = None) -> "FrozenWeight":
        """Freeze the weight side of `x @ w` gating at threshold `tau`.

        Runs the backend's get-norm ONCE (plus `levels` pooling reductions)
        — this is the offline "planning pass" that serving then never pays.

        compute_dtype freezes for low-precision execution: norms come from
        the quantized weight view (f32 norms OF the quantized values, the
        "compute the pyramid in f32 once at freeze time" half of
        quantization-aware gating), int8 stores the per-tile scale table,
        and `for_rows` widens the gate τ (kernels/quantize.py) so the
        low-precision gate is conservative w.r.t. the f32 gate at `tau`.
        """
        bk = kops.get_backend(backend)
        compute_dtype = kquant.canonical_dtype(compute_dtype)
        w = jnp.asarray(w)
        assert w.ndim == 2, w.shape
        k, n = w.shape
        wp = pad_to_tile(w, tile, tile * block_n)
        b_scale = None
        if compute_dtype == "int8":
            # fused absmax/scale + get-norm: quantized-view norms AND the
            # persisted b_scale table from one read of the padded weight
            base, b_scale = kops.int8_norms_and_scales(
                wp, tile, backend=bk.name, use_mxu=use_mxu)
        else:
            wv = (kquant.quantized_view(wp, compute_dtype, tile)
                  if compute_dtype != "float32" else wp)
            base = bk.norms(wv, tile, use_mxu=use_mxu)
        pyr = NormPyramid.from_normmap(base, levels, tile=tile)
        base_np = np.asarray(base, np.float32)
        gk, gnp = base_np.shape
        assert gnp % block_n == 0, (gnp, block_n)
        gnb = gnp // block_n
        nbmax = (base_np.reshape(gk, gnb, block_n).max(2)
                 if block_n > 1 else base_np)
        tau_f = float(np.asarray(tau))
        if tau_f > 0.0:
            # a zero-norm weight super-column can never pass `na·nb ≥ τ>0`
            # for any activation — frozen-safe weight-side pruning
            kk, jj = np.nonzero(nbmax > 0.0)
        else:
            kk, jj = [x.ravel() for x in
                      np.mgrid[0:gk, 0:gnb].astype(np.int64)]
        order = np.lexsort((kk, jj))  # (j asc, k asc) → pair-major steps
        return cls(
            jnp.asarray(tau_f, jnp.float32),
            tuple(jnp.asarray(lv) for lv in pyr.levels),
            jnp.asarray(nbmax),
            jnp.asarray(kk[order], jnp.int32),
            jnp.asarray(jj[order], jnp.int32),
            b_scale,
            tile=tile, block_n=block_n, num_levels=levels, backend=bk.name,
            wshape=(int(k), int(n)),
            padded=(int(wp.shape[0]), int(wp.shape[1])),
            use_mxu=use_mxu, weight_hash=weight_hash,
            compute_dtype=compute_dtype, tuned=tuned,
        )

    # -- k-blocking -----------------------------------------------------------
    def _row_steps(self, kb: int):
        """One row tile's steps at `kb` k-tiles a step: (j, k-block) over
        the admissible pairs, pair-major, ascending k-block."""
        hit = self._blocks.get(kb)
        if hit is None:
            kj_k = np.asarray(self.kj_k, np.int32)
            kj_j = np.asarray(self.kj_j, np.int32)
            hit = kcost.block_steps(np.zeros_like(kj_j), kj_j, kj_k, kb)[1:3]
            self._blocks[kb] = hit
        return hit

    def real_steps(self, width: int, kb: int) -> int:
        """Real (non-padding) steps of a `width`-row-tile plan at `kb`."""
        return width * int(self._row_steps(kb)[0].size)

    def block_fill(self, kb: int) -> float:
        """Admissible tile products over the `steps · kb` the blocked
        tables cover — the kernel's fill when every activation tile
        passes (1.0 with no step)."""
        n = self._row_steps(kb)[0].size
        return self.num_kj / (n * kb) if n else 1.0

    def choose_kb(self, width: int) -> int:
        """k-tiles per kernel step for a `width`-row-tile plan: the cost
        model's argmin (`core.cost.choose_kb`) over the admissible triples,
        priced with an all-pass activation as `core.cost.tune` prices
        frozen plans. 1 on backends without a work-list kernel."""
        hit = self._kb_cache.get(width)
        if hit is not None:
            return hit
        bk = kops.get_backend(self.backend)
        kb = 1
        if bk.matmul_worklist is not None and self.num_kj:
            kj_k = np.asarray(self.kj_k, np.int32)
            kj_j = np.asarray(self.kj_j, np.int32)
            gk, _ = self.grid
            kb = kcost.choose_kb(
                np.repeat(np.arange(width, dtype=np.int32), kj_k.size),
                np.tile(kj_j, width), np.tile(kj_k, width), gk=gk,
                tile=self.tile, block_n=self.block_n,
                dtype=self.compute_dtype, bucket_min=self.bucket_floor,
                coeffs=kcost.CostProfile().coeffs(bk.name))
        self._kb_cache[width] = kb
        return kb

    # -- shape specialization -----------------------------------------------
    def for_rows(self, gm: int, *, min_steps: int = 0,
                 kb: Optional[int] = None) -> "FrozenPlan":
        """Specialize to an activation row grid of `gm` tiles.

        Emits the step tables pair-major ((i, j) runs contiguous, k
        ascending within a run) exactly like `compact_from_triples`, padded
        to a power-of-two bucket of at least max(`min_steps`,
        `bucket_floor`) — the floor is the autotuned per-weight bucket when
        present; pass a common `min_steps` when plans of several weights
        must stack into one scan input. Padding steps repeat the last real
        step with the `real` bit clear, so the traced gate can never
        activate them. Each step covers `kb` k-tiles (default: this
        weight's `choose_kb(gm)`; pass a common `kb` when plans must
        stack). Cached per (gm, bucket, kb).

        Shape-bucketed serving leans on this cache: the engine rounds its
        slot pool to a power of two (`cost.bucket`), so a sweep of
        arbitrary batch shapes resolves to at most
        `len(cost.bucket_ladder(max_batch, 1))` distinct `gm` values —
        O(buckets) specializations and jit traces, not O(shapes)."""
        return self._specialize(gm, gm, min_steps, kb)

    def slice_rows(self, lo: int, hi: int, *, gm: Optional[int] = None,
                   min_steps: int = 0, kb: Optional[int] = None
                   ) -> "FrozenPlan":
        """The per-shard plan of row-tile strip [lo, hi) on a LOCAL grid of
        `gm` tiles (≥ the strip width; default = the width) — what a
        shard_map'd step consumes when a variable-width row partition
        assigns this weight's activation rows [lo·tile, hi·tile) to one
        device, clamp-padded so every shard shares one static shape.

        The step tables enumerate all weight-admissible (k, j) pairs per
        LOCAL row tile 0..hi-lo (a shard's rows are renumbered from 0; the
        weight-side pair list is activation-row-agnostic, so the strip's
        real content depends only on its width — (lo, hi) names the strip
        and validates the cut). Local tiles ≥ hi-lo are clamp padding: no
        step targets them (`real` is clear beyond the strip's steps), so
        pad rows do ZERO gated work — the per-shard work difference IS the
        load-balance mechanism. Pass a common `min_steps` bucket (computed
        at the PADDED width) and a common `kb` (default: `choose_kb` at the
        padded width) so per-shard plans of one weight stack; built
        host-side at re-shard time, never in-trace."""
        if not 0 <= lo <= hi:
            raise ValueError(f"bad row strip [{lo}, {hi})")
        width = hi - lo
        gm = width if gm is None else gm
        if gm < width:
            raise ValueError(
                f"local grid {gm} smaller than strip width {width}")
        return self._specialize(width, gm, min_steps,
                                self.choose_kb(gm) if kb is None else kb)

    def shard_by_offsets(self, offsets, *, width: Optional[int] = None,
                         min_steps: int = 0) -> "FrozenPlan":
        """Stack per-shard `slice_rows` plans of a variable-width partition
        (`offsets` as cut by `schedule.equal_work_partition` / rescaled by
        `schedule.rescale_offsets`, in this weight's row-tile units) into
        ONE FrozenPlan whose children carry a leading shard dim — the
        pytree a shard_map'd step takes with every leaf sharded on dim 0.

        `width` fixes the common local grid (≥ the widest strip; default =
        the widest strip): the engine pins it per wave so every re-cut
        yields identical shapes (recompile-free swap). All shards share one
        step bucket and one kb, both fixed at the padded width, so their
        static metadata is identical by construction."""
        offs = np.asarray(offsets, np.int64)
        if offs.ndim != 1 or offs.shape[0] < 2 or offs[0] != 0 \
                or np.any(np.diff(offs) < 1):
            raise ValueError(f"malformed offset table {offs}")
        wmax = int(np.diff(offs).max())
        if width is not None:
            if width < wmax:
                raise ValueError(
                    f"fixed width {width} < widest strip {wmax}")
            wmax = int(width)
        kb = self.choose_kb(wmax)
        bucket = _bucket(max(self.real_steps(wmax, kb), min_steps),
                         self.bucket_floor)
        shards = [
            self.slice_rows(int(offs[d]), int(offs[d + 1]), gm=wmax,
                            min_steps=bucket, kb=kb)
            for d in range(offs.shape[0] - 1)
        ]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)

    def _specialize(self, width: int, gm: int, min_steps: int,
                    kb: Optional[int] = None) -> "FrozenPlan":
        """Shared body of `for_rows` (width == gm) and `slice_rows` (width ≤
        gm: real steps cover local tiles [0, width), tiles beyond are
        untargeted clamp padding)."""
        gk, gnb = self.grid
        kb = self.choose_kb(gm) if kb is None else kb
        j, kblk = self._row_steps(kb)
        s_real = width * j.size
        s = _bucket(max(s_real, min_steps), self.bucket_floor)
        key = (width, gm, s, kb)
        hit = self._rows_cache.get(key)
        if hit is not None:
            return hit
        tables = _step_tables(
            np.repeat(np.arange(width, dtype=np.int32), j.size),
            np.tile(j, width), np.tile(kblk, width), s, gnb)
        # the FrozenPlan's tau is the GATE threshold: for low-precision
        # artifacts that is the quantization-widened τ' ≤ τ, so the traced
        # gate over quantized norms keeps a superset of the f32-gated set
        # (self.tau stays the requested τ — the store-addressing value)
        gate_tau = kquant.widen_tau(
            float(np.asarray(self.tau)), self.compute_dtype, self.tile)
        fp = FrozenPlan(
            jnp.asarray(gate_tau, jnp.float32), self.levels[0], self.nbmax,
            *(jnp.asarray(t) for t in tables),
            self.b_scale,
            tile=self.tile, block_n=self.block_n, num_levels=self.num_levels,
            backend=self.backend, gm=gm, gk=gk, gnb=gnb,
            wshape=self.wshape, version=self.version,
            compute_dtype=self.compute_dtype, kb=kb,
        )
        self._rows_cache[key] = fp
        return fp


def _step_tables(step_i, step_j, step_k, s: int, gnb: int):
    """A FrozenPlan's step tables from its real steps, padded to `s`:
    (step_i, step_j, step_k, step_real, seg_first, seg_last) numpy."""
    s_real = step_i.size
    if s_real:
        pad = s - s_real
        step_i, step_j, step_k = (
            np.concatenate([t, np.full(pad, t[-1])]).astype(np.int32)
            for t in (step_i, step_j, step_k))
    else:
        step_i = np.zeros(s, np.int32)
        step_j = np.zeros(s, np.int32)
        step_k = np.zeros(s, np.int32)
    step_real = np.zeros(s, bool)
    step_real[:s_real] = True
    # segment (= output pair) runs over the PADDED tables: padding
    # repeats the last real (i, j), so it merges into the final run and
    # the in-trace flag arithmetic needs no special cases
    pair = step_i.astype(np.int64) * gnb + step_j
    new = np.ones(s, bool)
    new[1:] = pair[1:] != pair[:-1]
    starts = np.flatnonzero(new)
    counts = np.diff(np.append(starts, s))
    ends = np.append(starts[1:], s) - 1
    seg_first = np.repeat(starts, counts).astype(np.int32)
    seg_last = np.repeat(ends, counts).astype(np.int32)
    return step_i, step_j, step_k, step_real, seg_first, seg_last


@jax.tree_util.register_pytree_node_class
class FrozenPlan:
    """A FrozenWeight specialized to one activation row grid — THE pytree a
    jitted prefill/decode step takes as an argument.

    Array fields (children; concrete when built, tracers inside the jit):
      tau          f32 scalar
      norm_b       (gk, gnp) weight-side finest normmap (plan metadata /
                   execute shape contract)
      nbmax        (gk, gnb) per-super-column max norms — the traced gate's
                   weight half
      step_i/j/k   (S,) int32 — pair-major ascending-k step tables over ALL
                   weight-admissible (i, j, k-block): one step per k-block
                   of `kb` k-tiles that holds an admissible pair (kb = 1:
                   one per pair, S = gm·W), bucket-padded
      step_real    (S,) bool — clear on bucket padding steps
      seg_first/seg_last (S,) int32 — index of the first/last step of each
                   step's (i, j) segment: what lets the traced activation
                   gate derive INIT/FLUSH flags with pure static-shape
                   cumsum/gather arithmetic
      b_scale      (gk, gnp) f32 int8 weight scale table, or None — rides
                   into the SpammPlan so execute quantizes the weight with
                   the frozen scales (bit-stable across restarts)

    NOTE: `tau` here is the GATE threshold — for low-precision artifacts the
    quantization-widened τ', not the requested τ (which lives on the
    FrozenWeight / in the store address).

    Static metadata (aux): tile, block_n, num_levels, backend, gm, gk, gnb,
    wshape, version, compute_dtype, kb. Leading batch dims on every child
    are allowed (stacked per-layer plans riding a lax.scan — see
    `stack_plans`).
    """

    def __init__(self, tau, norm_b, nbmax, step_i, step_j, step_k, step_real,
                 seg_first, seg_last, b_scale=None, *, tile: int,
                 block_n: int, num_levels: int, backend: str, gm: int,
                 gk: int, gnb: int, wshape: Tuple[int, int],
                 version: int = PLAN_FORMAT_VERSION,
                 compute_dtype: str = "float32", kb: int = 1):
        self.tau = tau
        self.norm_b = norm_b
        self.nbmax = nbmax
        self.step_i = step_i
        self.step_j = step_j
        self.step_k = step_k
        self.step_real = step_real
        self.seg_first = seg_first
        self.seg_last = seg_last
        self.b_scale = b_scale
        self.tile = tile
        self.block_n = block_n
        self.num_levels = num_levels
        self.backend = backend
        self.gm = gm
        self.gk = gk
        self.gnb = gnb
        self.wshape = tuple(wshape)
        self.version = version
        self.compute_dtype = compute_dtype
        self.kb = kb

    def tree_flatten(self):
        children = (self.tau, self.norm_b, self.nbmax, self.step_i,
                    self.step_j, self.step_k, self.step_real, self.seg_first,
                    self.seg_last, self.b_scale)
        aux = (self.tile, self.block_n, self.num_levels, self.backend,
               self.gm, self.gk, self.gnb, self.wshape, self.version,
               self.compute_dtype, self.kb)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (tile, block_n, num_levels, backend, gm, gk, gnb, wshape, ver,
         dtype, kb) = aux
        return cls(*children, tile=tile, block_n=block_n,
                   num_levels=num_levels, backend=backend, gm=gm, gk=gk,
                   gnb=gnb, wshape=wshape, version=ver, compute_dtype=dtype,
                   kb=kb)

    @property
    def num_steps(self) -> int:
        return self.step_i.shape[-1]

    def _real_steps_at(self, kb: int):
        """This (unstacked, concrete) plan's real steps re-cut to `kb`
        k-tiles a step, kb dividing self.kb: each k-block splits into
        self.kb // kb, kept where it holds a weight-admissible k-tile — the
        rule `FrozenWeight.build` froze (τ > 0: a nonzero weight norm), so
        the result equals the weight's own tables at `kb`."""
        real = np.asarray(self.step_real)
        si, sj, sk = (np.asarray(t)[real] for t in
                      (self.step_i, self.step_j, self.step_k))
        r = self.kb // kb
        assert r * kb == self.kb, (self.kb, kb)
        si, sj = np.repeat(si, r), np.repeat(sj, r)
        sk = (sk[:, None] * r + np.arange(r)).reshape(-1)
        if float(np.asarray(self.tau)) > 0.0:
            ks = sk[:, None] * kb + np.arange(kb)
            nb = np.asarray(self.nbmax)
            keep = (nb[ks, sj[:, None]] > 0.0).any(1)
            si, sj, sk = si[keep], sj[keep], sk[keep]
        return si, sj, sk

    def restep(self, kb: int, num_steps: int) -> "FrozenPlan":
        """This plan at `kb` k-tiles a step (dividing its own), padded to
        `num_steps` — how `stack_plans` brings layers to one kb and one
        bucket. Host-side, on a concrete unstacked plan."""
        if kb == self.kb and num_steps == self.num_steps:
            return self
        si, sj, sk = self._real_steps_at(kb)
        if si.size > num_steps:
            raise ValueError(f"{si.size} steps do not fit {num_steps}")
        tables = _step_tables(si, sj, sk, num_steps, self.gnb)
        children = list(self.tree_flatten()[0])
        children[3:9] = [jnp.asarray(t) for t in tables]
        aux = self.tree_flatten()[1][:-1] + (kb,)  # kb is aux's last
        return FrozenPlan.tree_unflatten(aux, children)


def freeze_weight(w, tau, *, tile: int = 64, block_n: int = 1,
                  levels: int = 0, backend: str = "auto",
                  use_mxu: bool = False, weight_hash: str = "",
                  compute_dtype: str = "float32",
                  tuned: TunedParams | None = None) -> FrozenWeight:
    """Convenience alias for `FrozenWeight.build`."""
    return FrozenWeight.build(w, tau, tile=tile, block_n=block_n,
                              levels=levels, backend=backend, use_mxu=use_mxu,
                              weight_hash=weight_hash,
                              compute_dtype=compute_dtype, tuned=tuned)


def stack_plans(fps) -> FrozenPlan:
    """Stack per-layer FrozenPlans (same static metadata, same bucket — use
    `for_rows(gm, min_steps=..., kb=...)` with a common bucket and kb) into
    ONE plan whose children carry a leading layer dim: the shape lax.scan
    slices per step, which is how frozen plans ride a scanned-layer prefill.

    Layers whose kb differ are first re-cut to the smallest of them and
    padded to one bucket (`FrozenPlan.restep`): one kernel runs the scan."""
    fps = list(fps)
    assert fps, "stack_plans of nothing"
    kb = min(fp.kb for fp in fps)
    if any(fp.kb != kb for fp in fps):
        steps = [fp._real_steps_at(kb)[0].size for fp in fps]
        s = max(max(_bucket(n, 1) for n in steps),
                max(fp.num_steps for fp in fps))
        fps = [fp.restep(kb, s) for fp in fps]
    aux0 = fps[0].tree_flatten()[1]
    for fp in fps[1:]:
        assert fp.tree_flatten()[1] == aux0, (
            "stack_plans needs identical static metadata (shapes/bucket): "
            f"{fp.tree_flatten()[1]} != {aux0}")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *fps)


# row tiles an expert's plan is priced at when its k-block is chosen: a
# decode step's held expert sees one row tile, a prefill chunk's a few
EXPERT_KB_WIDTH = 8


@jax.tree_util.register_pytree_node_class
class FrozenExperts:
    """Frozen weight sides of one MoE layer's held experts at one site
    (w1, w3 or w2): what the expert layer's gated GEMM takes as a jit
    argument. Row tiles of the routed-row operand belong to experts only at
    run time, so the step tables are per expert and the traced gate
    expands them per row tile (`core.module.spamm_experts_frozen`).

    Array fields (children; a leading layer dim when stacked):
      tau     f32 scalar, the gate threshold
      nbmax   (E, gk, gn) f32 — each expert's weight tile norms
      st_j/st_k (E, W) int32 — one row tile's steps per expert: (j, k-block)
              over the weight-admissible pairs, pair-major, ascending
              k-block; entries past `st_n` repeat the last real step
      st_n    (E,) int32 — real steps per expert

    Static metadata (aux): tile, kb, gk, gn, backend."""

    def __init__(self, tau, nbmax, st_j, st_k, st_n, *, tile: int, kb: int,
                 gk: int, gn: int, backend: str):
        self.tau = tau
        self.nbmax = nbmax
        self.st_j = st_j
        self.st_k = st_k
        self.st_n = st_n
        self.tile = tile
        self.kb = kb
        self.gk = gk
        self.gn = gn
        self.backend = backend

    def tree_flatten(self):
        return ((self.tau, self.nbmax, self.st_j, self.st_k, self.st_n),
                (self.tile, self.kb, self.gk, self.gn, self.backend))

    @classmethod
    def tree_unflatten(cls, aux, children):
        tile, kb, gk, gn, backend = aux
        return cls(*children, tile=tile, kb=kb, gk=gk, gn=gn,
                   backend=backend)

    @property
    def steps_per_tile(self) -> int:
        return self.st_j.shape[-1]


def freeze_experts(fws, width: int = EXPERT_KB_WIDTH):
    """Group per-expert `FrozenWeight`s into `FrozenExperts`.

    `fws` is a list (one per layer) of lists (one per held expert). Every
    layer gets one kb (the smallest any expert's cost model picks at
    `width` row tiles) and one step count per row tile, so the layers stack
    into one scan input. Returns the list of per-layer FrozenExperts."""
    flat = [fw for layer in fws for fw in layer]
    fw0 = flat[0]
    if any(fw.block_n != 1 or fw.compute_dtype != "float32" for fw in flat):
        raise ValueError("expert plans are frozen at block_n 1 in float32")
    kb = min(fw.choose_kb(width) for fw in flat)
    steps = [[fw._row_steps(kb) for fw in layer] for layer in fws]
    w = max(max(j.size for j, _ in layer) for layer in steps)
    gk, gn = fw0.grid
    out = []
    for layer, tables in zip(fws, steps):
        e = len(layer)
        st_j = np.zeros((e, w), np.int32)
        st_k = np.zeros((e, w), np.int32)
        st_n = np.zeros(e, np.int32)
        for x, (j, k) in enumerate(tables):
            n = j.size
            st_n[x] = n
            if n:
                st_j[x, :n], st_j[x, n:] = j, j[-1]
                st_k[x, :n], st_k[x, n:] = k, k[-1]
        out.append(FrozenExperts(
            jnp.asarray(float(np.asarray(fw0.tau)), jnp.float32),
            jnp.stack([fw.nbmax for fw in layer]),
            jnp.asarray(st_j), jnp.asarray(st_k), jnp.asarray(st_n),
            tile=fw0.tile, kb=kb, gk=gk, gn=gn, backend=fw0.backend))
    return out
