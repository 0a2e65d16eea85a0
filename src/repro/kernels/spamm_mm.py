"""Pallas TPU multiplication kernel (paper §3.3, Alg. 2/3).

C[i,j] = sum over *valid* k of A[i,k] @ B[k,j], where validity is the norm
test normA[i,k] * normB[k,j] >= tau computed by the get-norm kernel.

TPU-native mapping of the paper's design — two entry points:

`spamm_mm` (dense-grid): walks the full (gm, gn, gk) grid and masks invalid
steps out.

  * paper `map_offset` (Fig. 3b — compacted list of valid k's so the bitmap
    walk is contiguous)  →  an int32 scalar-prefetch table `kidx[i, j, t]`
    (t-th valid k for output tile (i,j)) driving the BlockSpec index_maps.
    Padding slots repeat the last valid k; Pallas' revisiting optimization
    sees an unchanged block index and skips the HBM→VMEM copy, so an invalid
    step costs ~nothing — the same effect as the paper's "prefetch only valid
    blocks" but implemented in the pipeline itself.
  * paper double buffering (half-block prefetch / half-block compute)  →
    Pallas' built-in multi-buffered grid pipeline.
  * paper per-thread register accumulation  →  a persistent f32 VMEM scratch
    accumulator revisited across the (arbitrary) k grid dimension.
  * paper tensor-core path (Alg. 3, fp16 fragments / fp32 accumulator)  →
    bf16 inputs into the MXU via jnp.dot(..., preferred_element_type=f32).

`spamm_mm_worklist` (ragged, the paper-faithful "iterate only valid
products" form): a 1-D grid over the plan's flattened work-list — one grid
step per k-block of `kb` consecutive k-tiles that holds a surviving
(i, j, k) triple, padded to a bucket instead of gm·gn·gk. Four
scalar-prefetch tables (step_i/step_j/step_k/step_flags, built once by
`repro.core.plan.compact_from_triples`) drive the BlockSpec index_maps;
per-step flag bits init/accumulate/flush the VMEM accumulator at (i,
j)-group boundaries and name the step's surviving k-tiles. A grid step has
a fixed cost (about 0.35 µs on a v5e) that one 128³ tile dot does not
cover, so the planners cover up to 16 k-tiles a step where the gate keeps
them (`repro.core.cost.choose_kb`). Output tiles with no valid product
are never visited — the out buffer aliases a zeros array so they stay
exactly zero. Heavily-pruned products therefore stop paying masked-out grid
steps entirely: execution cost is proportional to valid work, which is the
paper's map_offset design carried all the way into the grid shape.

The gating itself (paper Alg. 2 lines 3–14) is built ONCE per product by
`repro.core.plan.plan` into a `SpammPlan` — for concrete operands the
compacted work-list comes straight from the hierarchical descent's
surviving triples (no dense-bitmap sort); traced plans fall back to the
dense `kidx` tables + `spamm_mm`. Serving callers reuse the plan
(weight-side artifacts via `repro.core.plan.WeightPlanCache`) across
repeated products.

Dtype contract (paper Alg. 3's tensor-core path, generalized):

  input dtype × accumulate dtype × flush cast — the accumulator is ALWAYS
  f32 in VMEM regardless of input dtype, and the FLUSH step casts it to
  `out_dtype` exactly once per output tile. Three input precisions:

  * f32:  `spamm_mm_worklist` as-is. The MXU multiplies at f32 (HIGHEST
          precision, `_mxu_dot`) and accumulates in f32.
  * bf16: the SAME `spamm_mm_worklist` entry point — pass bf16 `a`/`b` and
    the `jnp.dot(..., preferred_element_type=f32)` body feeds the MXU's
    native bf16×bf16→f32 path. No kernel change: the flag-bit step-table
    design is dtype-blind. On inputs exactly representable in bf16 the
    result is bit-identical to the f32 run (each product of two 8-bit
    significands is exact in f32, and the ascending-k accumulation order
    is unchanged); otherwise it differs only by the input rounding, which
    the quantization-aware gate accounts for (kernels/quantize.py).
  * int8: `spamm_mm_worklist_int8` — symmetric per-(tile × tile)-tile
    quantized operands (see kernels/quantize.py: q = clip(round(x/scale)),
    scale = amax/127) with two extra f32 scalar-prefetch tables `a_scale`
    (gm, gk) and `b_scale` (gk, gn_fine; PER FINE TILE even when
    block_n > 1, so the dequantization and the gate's error bound stay at
    tile granularity). Each step does an int8×int8→int32 MXU dot, then
    scales into the f32 accumulator: acc += i32 · a_scale[i,k] ·
    b_scale[k, j·block_n + c] per fine output column group c. The flush
    cast and zero-aliasing behavior are identical to the f32 kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (check_smem, check_tile, check_vmem,
                                  mesh_vma, varying, worklist_vmem_bytes)


def _mxu_dot(a, b):
    """One tile product into the f32 accumulator. f32 operands multiply at
    f32 (HIGHEST): Mosaic's default for them is one bf16 pass, measured at
    3.2e-3 of max |C| on a v5e at n = 4096 against 4.2e-7 for f32. bf16
    operands take their native single pass (products exact in f32). The
    precision is explicit either way, so a caller's
    `jax.default_matmul_precision` cannot reach the kernel body."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _spamm_mm_kernel(kidx_ref, nv_ref, a_ref, b_ref, o_ref, acc_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # paper Alg. 2 line 19: iterate only over valid products; here invalid
    # trailing steps are masked out (their block fetches are revisits = free).
    @pl.when(t < nv_ref[i, j])
    def _compute():
        acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])

    @pl.when(t == nt - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "out_dtype", "interpret", "block_n"),
)
def spamm_mm(
    a: jax.Array,
    b: jax.Array,
    kidx: jax.Array,
    nvalid: jax.Array,
    *,
    tile: int = 64,
    block_n: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Masked tiled matmul driven by compacted valid-k lists.

    a: (M, K); b: (K, N); kidx: (gm, gn, gk) int32; nvalid: (gm, gn) int32,
    where gm = M//tile, gk = K//tile, gn = N//tile (see spamm_compact_ref).

    block_n: number of consecutive B/C tiles handled per grid step in the N
    dimension (wider MXU blocks → better arithmetic intensity; requires the
    *same* kidx for the grouped j's, i.e. kidx/nvalid built at block_n
    granularity — callers get both from `repro.core.plan.plan`, which
    builds the super-column mask and its compaction in one place).
    Returns C: (M, N) in out_dtype (f32 accumulate regardless of input dtype).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    gm, gk = m // tile, k // tile
    gn = n // (tile * block_n)
    assert kidx.shape == (gm, gn, gk), (kidx.shape, (gm, gn, gk))
    assert nvalid.shape == (gm, gn)
    if not interpret:
        check_tile(tile)
        check_smem("spamm_mm", kidx, nvalid)

    grid = (gm, gn, gk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, tile), lambda i, j, t, kidx, nv: (i, kidx[i, j, t])),
            pl.BlockSpec(
                (tile, tile * block_n), lambda i, j, t, kidx, nv: (kidx[i, j, t], j)
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile * block_n), lambda i, j, t, kidx, nv: (i, j)
        ),
        scratch_shapes=[pltpu.VMEM((tile, tile * block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _spamm_mm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (m, n), out_dtype, vma=mesh_vma(a, b, kidx, nvalid)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spamm_mm",
    )(kidx, nvalid, a, b)


# step_flags bits (see repro.core.plan.compact_from_triples, which builds the
# tables): INIT zeroes the accumulator (first step of an (i, j) group), ACC
# marks a step with work (every real step; bucket-padding steps have no bits
# set), FLUSH writes the accumulator to the output tile (last step of a
# group). A step that covers kb > 1 k-tiles carries which of them survive
# the gate in bits STEP_SUB .. STEP_SUB + kb - 1 (bit STEP_SUB + c: k-tile
# c of the step's k-block); at kb = 1 ACC alone says it.
STEP_INIT, STEP_ACC, STEP_FLUSH = 1, 2, 4
STEP_SUB = 3
KB_MAX = 16  # the sub-tile mask must fit the int32 flags beside bits 0-2


def _spamm_mm_worklist_kernel(
    si_ref, sj_ref, sk_ref, fl_ref, zero_ref, a_ref, b_ref, o_ref, acc_ref,
    *, kb: int, tile: int,
):
    del zero_ref  # only aliased into o_ref so unvisited tiles stay zero
    s = pl.program_id(0)
    f = fl_ref[s]

    @pl.when((f & STEP_INIT) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # paper Alg. 2 line 19, taken literally: every grid step holds valid
    # products (bucket-padding steps revisit the last real blocks — free —
    # and carry no flag bits, so they neither accumulate nor flush)
    if kb == 1:
        @pl.when((f & STEP_ACC) != 0)
        def _compute():
            acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])
    else:
        # one tile dot per surviving k-tile, in ascending k: the same f32
        # additions in the same order as kb one-tile steps, so C is
        # bit-identical to the kb = 1 kernel on the same gate. A full
        # k-block runs unrolled and branch-free, which lets its dots
        # overlap (0.19 µs a dot on a v5e against 0.27 through the
        # per-k-tile branch); a partial one loops, which halves the
        # kernel's compile time against unrolling that branch too
        full = ((1 << kb) - 1) << STEP_SUB
        bits = f & full

        @pl.when(bits == full)
        def _all():
            acc = acc_ref[...]
            for c in range(kb):
                cols = slice(c * tile, (c + 1) * tile)
                acc = acc + _mxu_dot(a_ref[:, cols], b_ref[cols, :])
            acc_ref[...] = acc

        @pl.when(bits != full)
        def _some():
            def one(c, carry):
                @pl.when(((f >> (STEP_SUB + c)) & 1) != 0)
                def _():
                    at = pl.multiple_of(c * tile, tile)
                    acc_ref[...] += _mxu_dot(a_ref[:, pl.ds(at, tile)],
                                             b_ref[pl.ds(at, tile), :])
                return carry

            jax.lax.fori_loop(0, kb, one, 0)

    @pl.when((f & STEP_FLUSH) != 0)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "out_dtype", "interpret", "block_n", "kb"),
)
def spamm_mm_worklist(
    a: jax.Array,
    b: jax.Array,
    step_i: jax.Array,
    step_j: jax.Array,
    step_k: jax.Array,
    step_flags: jax.Array,
    *,
    tile: int = 64,
    block_n: int = 1,
    kb: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Ragged masked matmul: 1-D grid over the compacted work-list.

    a: (M, K); b: (K, N). step_i/step_j/step_k/step_flags: (S,) int32 tables,
    one entry per grid step in (i, j)-grouped ascending-k order, padded to a
    bucket (padding entries repeat the last real step with flags 0). Built
    by `repro.core.plan.compact_from_triples` straight from the planner's
    surviving triples, or by `repro.plans.frozen` for frozen plans.

    `kb` k-tiles per grid step: `step_k` is then a k-block id, the step
    multiplies the (tile, kb·tile) A block by the (kb·tile, tile·block_n) B
    block one tile dot at a time, and the flags' sub-tile bits (STEP_SUB)
    say which of the kb tile dots survive. K must split into whole k-blocks.

    `step_j` is a super-column id when block_n > 1 (each grid step computes a
    (tile, tile·block_n) output block). The grid has length S, NOT gm·gn·gk —
    pruned products cost nothing, and output tiles with no valid k stay zero
    via the aliased zero-initialized output. f32 accumulation in ascending-k
    order makes the result bit-identical to `spamm_mm` on the same mask, at
    every kb. Returns C: (M, N) in out_dtype.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % tile == 0 and k % (tile * kb) == 0 and n % (tile * block_n) == 0, (
        a.shape, b.shape, tile, kb, block_n)
    assert 1 <= kb <= KB_MAX, kb
    s = step_i.shape[0]
    assert step_j.shape == step_k.shape == step_flags.shape == (s,)
    if not interpret:
        check_tile(tile)
        check_smem("spamm_mm_worklist", step_i, step_j, step_k, step_flags)
        check_vmem("spamm_mm_worklist", worklist_vmem_bytes(
            tile, kb, block_n, a.dtype.itemsize, jnp.dtype(out_dtype).itemsize))

    tk, tn = tile * kb, tile * block_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[
            # zero output seed — same index map as the output so the aliased
            # HBM buffer is simply revisited
            pl.BlockSpec((tile, tn), lambda s, si, sj, sk, fl: (si[s], sj[s])),
            # A's block index holds across the consecutive j of a row tile
            # when K is one k-block, and Pallas then skips its re-fetch
            pl.BlockSpec((tile, tk), lambda s, si, sj, sk, fl: (si[s], sk[s])),
            pl.BlockSpec((tk, tn), lambda s, si, sj, sk, fl: (sk[s], sj[s])),
        ],
        out_specs=pl.BlockSpec(
            (tile, tn), lambda s, si, sj, sk, fl: (si[s], sj[s])),
        scratch_shapes=[pltpu.VMEM((tile, tn), jnp.float32)],
    )
    vma = mesh_vma(a, b, step_i, step_j, step_k, step_flags)
    return pl.pallas_call(
        functools.partial(_spamm_mm_worklist_kernel, kb=kb, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma),
        # index 4 counts the scalar-prefetch tables: the zeros operand seeds
        # the output buffer, so (i, j) tiles the work-list never visits are
        # zero rather than uninitialized
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="spamm_mm_worklist",
    )(step_i, step_j, step_k, step_flags,
      varying(jnp.zeros((m, n), out_dtype), vma), a, b)


def _spamm_mm_worklist_int8_kernel(
    si_ref, sj_ref, sk_ref, fl_ref, sa_ref, sb_ref,
    zero_ref, a_ref, b_ref, o_ref, acc_ref, *, block_n: int,
):
    del zero_ref  # only aliased into o_ref so unvisited tiles stay zero
    s = pl.program_id(0)
    f = fl_ref[s]

    @pl.when((f & STEP_INIT) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((f & STEP_ACC) != 0)
    def _compute():
        i, j, kk = si_ref[s], sj_ref[s], sk_ref[s]
        # int8 × int8 → int32 on the MXU (the tensor-core IMMA shape of
        # paper Alg. 3), then dequantize into the f32 accumulator
        prod = jax.lax.dot_general(
            a_ref[...], b_ref[...],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32) * sa_ref[i, kk]
        t = acc_ref.shape[0]
        if block_n == 1:
            acc_ref[...] += prod * sb_ref[kk, j]
        else:
            # b scales are per FINE tile: static unroll over the block_n
            # column groups of the (tile, tile·block_n) super-column block
            sb = jnp.stack(
                [sb_ref[kk, j * block_n + c] for c in range(block_n)]
            )  # (block_n,)
            prod = prod.reshape(t, block_n, t) * sb[None, :, None]
            acc_ref[...] += prod.reshape(t, block_n * t)

    @pl.when((f & STEP_FLUSH) != 0)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "out_dtype", "interpret", "block_n"),
)
def spamm_mm_worklist_int8(
    a_q: jax.Array,
    b_q: jax.Array,
    a_scale: jax.Array,
    b_scale: jax.Array,
    step_i: jax.Array,
    step_j: jax.Array,
    step_k: jax.Array,
    step_flags: jax.Array,
    *,
    tile: int = 64,
    block_n: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Int8 ragged masked matmul: the work-list kernel at IMMA precision.

    a_q: (M, K) int8, b_q: (K, N) int8 — symmetric per-(tile × tile)-tile
    quantized (kernels/quantize.py). a_scale: (M//tile, K//tile) f32,
    b_scale: (K//tile, N//tile) f32 — note b_scale is per FINE tile even
    when block_n > 1 (the kernel unrolls the block_n column groups), so the
    gate's per-tile error bound holds at tile granularity. Step tables as in
    `spamm_mm_worklist`. Accumulation is f32 in VMEM (int32 MXU products ×
    scales), cast to out_dtype on FLUSH. C ≈ dequant(a_q) @ dequant(b_q)
    restricted to the work-list: each int32 tile product is EXACT (no f32
    rounding inside the tile dot, unlike running the f32 kernel on the
    dequantized operands), so the two differ only by f32 multiply/add
    rounding — within a few ulps of each other.
    """
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2, (a_q.shape, b_q.shape)
    assert m % tile == 0 and k % tile == 0 and n % (tile * block_n) == 0, (
        a_q.shape, b_q.shape, tile, block_n)
    gm, gk, gn = m // tile, k // tile, n // tile
    assert a_scale.shape == (gm, gk), (a_scale.shape, (gm, gk))
    assert b_scale.shape == (gk, gn), (b_scale.shape, (gk, gn))
    s = step_i.shape[0]
    assert step_j.shape == step_k.shape == step_flags.shape == (s,)
    if not interpret:
        check_tile(tile)
        check_smem("spamm_mm_worklist_int8", step_i, step_j, step_k,
                   step_flags, a_scale, b_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(s,),
        in_specs=[
            pl.BlockSpec(
                (tile, tile * block_n),
                lambda s, si, sj, sk, fl, sa, sb: (si[s], sj[s]),
            ),
            pl.BlockSpec(
                (tile, tile), lambda s, si, sj, sk, fl, sa, sb: (si[s], sk[s])
            ),
            pl.BlockSpec(
                (tile, tile * block_n),
                lambda s, si, sj, sk, fl, sa, sb: (sk[s], sj[s]),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile * block_n),
            lambda s, si, sj, sk, fl, sa, sb: (si[s], sj[s]),
        ),
        scratch_shapes=[pltpu.VMEM((tile, tile * block_n), jnp.float32)],
    )
    kernel = functools.partial(_spamm_mm_worklist_int8_kernel, block_n=block_n)
    vma = mesh_vma(a_q, b_q, a_scale, b_scale, step_i, step_j, step_k,
                   step_flags)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma),
        # index 6 counts the 6 scalar-prefetch tables; the zeros operand
        # seeds the aliased output buffer (unvisited tiles stay zero)
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="spamm_mm_worklist_int8",
    )(step_i, step_j, step_k, step_flags, a_scale, b_scale,
      varying(jnp.zeros((m, n), out_dtype), vma), a_q, b_q)


def _spamm_mm_worklist_moe_kernel(si_ref, sj_ref, sk_ref, sb_ref, fl_ref,
                                  *refs, kb: int, tile: int):
    del sb_ref  # read by the B index map only
    _spamm_mm_worklist_kernel(si_ref, sj_ref, sk_ref, fl_ref, *refs, kb=kb,
                              tile=tile)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "out_dtype", "interpret", "kb"),
)
def spamm_mm_worklist_moe(
    a: jax.Array,
    b: jax.Array,
    step_i: jax.Array,
    step_j: jax.Array,
    step_k: jax.Array,
    step_b: jax.Array,
    step_flags: jax.Array,
    *,
    tile: int = 64,
    kb: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """The work-list kernel over an expert layer's routed rows: each row
    tile of `a` belongs to one expert, and its tile products read that
    expert's weight.

    a: (M, K) routed rows, sorted by expert, each expert's segment padded
    to whole row tiles. b: (E·K, N), the experts' (K, N) weights stacked.
    Step s reads A block (step_i[s], step_k[s]) and B block (step_b[s],
    step_j[s]): `step_b` is the k-block of the stacked B, its row tile's
    expert's k-block `step_k[s]` (expert · K/(tile·kb) + step_k). Tables
    and flags as `spamm_mm_worklist`'s (one step per k-block of `kb`
    k-tiles); steps with no flag bit revisit the previous step's blocks
    and do nothing, so a caller pads its tables past the live row tiles by
    repeating the last live step. Output tiles no step flushes stay zero.
    Returns C: (M, N) in out_dtype."""
    m, k = a.shape
    kt, n = b.shape
    assert m % tile == 0 and k % (tile * kb) == 0 and n % tile == 0, (
        a.shape, b.shape, tile, kb)
    assert kt % k == 0, (a.shape, b.shape)
    assert 1 <= kb <= KB_MAX, kb
    s = step_i.shape[0]
    assert (step_j.shape == step_k.shape == step_b.shape == step_flags.shape
            == (s,))
    if not interpret:
        check_tile(tile)
        check_smem("spamm_mm_worklist_moe", step_i, step_j, step_k, step_b,
                   step_flags)
        check_vmem("spamm_mm_worklist_moe", worklist_vmem_bytes(
            tile, kb, 1, a.dtype.itemsize, jnp.dtype(out_dtype).itemsize))

    tk = tile * kb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((tile, tile),
                         lambda s, si, sj, sk, sb, fl: (si[s], sj[s])),
            pl.BlockSpec((tile, tk),
                         lambda s, si, sj, sk, sb, fl: (si[s], sk[s])),
            pl.BlockSpec((tk, tile),
                         lambda s, si, sj, sk, sb, fl: (sb[s], sj[s])),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile), lambda s, si, sj, sk, sb, fl: (si[s], sj[s])),
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
    )
    vma = mesh_vma(a, b, step_i, step_j, step_k, step_b, step_flags)
    return pl.pallas_call(
        functools.partial(_spamm_mm_worklist_moe_kernel, kb=kb, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma),
        # index 5 counts the scalar-prefetch tables (the zeros operand
        # seeds the output buffer, as in spamm_mm_worklist)
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="spamm_mm_worklist_moe",
    )(step_i, step_j, step_k, step_b, step_flags,
      varying(jnp.zeros((m, n), out_dtype), vma), a, b)
