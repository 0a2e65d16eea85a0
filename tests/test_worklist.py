"""Compacted (work-list) plan execution — ISSUE 3 tentpole coverage.

The work-list path must be bit-identical to the dense-mask/dense-kidx
oracles across block_n, ragged shapes, empty and full masks; the plan's
`work` field must agree with the legacy `spamm_compact_ref` compaction on
random masks; and the block_n padding fix must make odd-N products work
through every entry point.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import module as mod
from repro.core import plan as pl
from repro.core import spamm as cs
from repro.kernels import ops, ref
from repro.kernels import spamm_mm as smm


def _decay(m, n, seed, scale=0.4):
    rng = np.random.default_rng(seed)
    d = np.abs(np.arange(m)[:, None] - np.arange(n)[None, :])
    base = (scale / (d ** 0.5 + 1)).astype(np.float32)
    return jnp.asarray(base * rng.standard_normal((m, n)).astype(np.float32))


TAU32 = 4.0  # gates a real fraction on the _decay operands at tile=32


# ---------------------------------------------------------------------------
# work-list vs dense oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_n", [1, 2, 4])
@pytest.mark.parametrize("levels", [0, 2])
def test_worklist_bit_identical_to_dense_grid_kernel(block_n, levels):
    """The ragged kernel (Σnvalid-step grid) is bit-identical to the
    dense-grid kidx kernel on the same mask: same f32 accumulator, same
    ascending-k order, only the grid shape differs."""
    a, b = _decay(128, 160, 0), _decay(160, 256, 1)
    p = pl.plan(a, b, TAU32, tile=32, block_n=block_n, backend="interpret",
                levels=levels)
    assert p.work is not None  # concrete plans are compacted-first
    got = pl.execute(p, a, b)
    kidx, nv = ref.spamm_compact_ref(p.mask)
    want = smm.spamm_mm(a, b, kidx, nv, tile=32, block_n=block_n,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_n", [1, 2])
def test_worklist_matches_jnp_masked_einsum(block_n):
    a, b = _decay(96, 128, 2), _decay(128, 192, 3)
    p_i = pl.plan(a, b, TAU32, tile=32, block_n=block_n, backend="interpret")
    p_j = pl.plan(a, b, TAU32, tile=32, block_n=block_n, backend="jnp")
    np.testing.assert_array_equal(np.asarray(p_i.mask), np.asarray(p_j.mask))
    np.testing.assert_allclose(
        np.asarray(pl.execute(p_i, a, b)),
        np.asarray(pl.execute(p_j, a, b)),
        atol=2e-4,
    )


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_empty_mask_all_zero_output(backend):
    a, b = _decay(96, 96, 4), _decay(96, 96, 5)
    p = pl.plan(a, b, 1e9, tile=32, backend=backend)
    assert int(p.valid_tiles) == 0
    if p.work is not None and p.work.step_flags is not None:
        assert p.work.num_valid == 0 and p.work.num_pairs == 0
        # the first padding step must still init+flush so block (0, 0) is
        # WRITTEN with zeros on real TPU (its VMEM window is copied back
        # even when the kernel never stores)
        flags = np.asarray(p.work.step_flags)
        assert flags[0] == (smm.STEP_INIT | smm.STEP_FLUSH)
        assert np.all(flags[1:] == 0)
    c = pl.execute(p, a, b)
    assert np.all(np.asarray(c) == 0.0)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("block_n", [1, 2])
def test_full_mask_equals_dense_matmul(backend, block_n):
    a, b = _decay(64, 96, 6), _decay(96, 128, 7)
    p = pl.plan(a, b, -1.0, tile=32, block_n=block_n, backend=backend)
    assert float(p.valid_fraction) == 1.0
    c = pl.execute(p, a, b)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a @ b), atol=2e-3)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_ragged_shapes_through_spamm(backend):
    """Arbitrary (non-tile-multiple) shapes pad, execute, un-pad — identical
    to the reference blocked masked einsum on the padded operands."""
    a, b = _decay(70, 45, 8), _decay(45, 90, 9)
    c, info = cs.spamm(a, b, 1.5, tile=32, backend=backend)
    want = ref.spamm_matmul_ref(pl.pad_to_tile(a, 32), pl.pad_to_tile(b, 32),
                                1.5, 32)[:70, :90]
    assert 0.0 < float(info.valid_fraction) < 1.0
    np.testing.assert_allclose(np.asarray(c), np.asarray(want), atol=2e-4)


def test_plan_work_agrees_with_spamm_compact_ref():
    """Random masks: kidx/nvalid derived from `plan().work` equal the legacy
    dense-bitmap sort compaction, including padding-slot layout."""
    rng = np.random.default_rng(10)
    for trial in range(5):
        gm, gn, gk = rng.integers(1, 7, 3)
        na = jnp.asarray(rng.uniform(0, 1, (gm, gk)).astype(np.float32))
        nb = jnp.asarray(rng.uniform(0, 1, (gk, gn)).astype(np.float32))
        tau = float(rng.uniform(0.05, 0.8))
        p = pl.plan(None, None, tau, norm_a=na, norm_b=nb, tile=32,
                    backend="interpret")
        kidx_ref, nv_ref = ref.spamm_compact_ref(
            ref.spamm_mask_ref(na, nb, jnp.float32(tau)))
        np.testing.assert_array_equal(
            pl.kidx_from_work(p.work, gm, gn, gk), np.asarray(kidx_ref))
        np.testing.assert_array_equal(
            np.asarray(p.nvalid), np.asarray(nv_ref))
        # pair/step views are mutually consistent
        w = p.work
        assert int(np.asarray(w.offsets)[-1]) == w.num_valid
        assert int(p.valid_tiles) == w.num_valid


def test_worklist_step_tables_bucketed_and_flagged():
    a, b = _decay(128, 128, 11), _decay(128, 128, 12)
    p = pl.plan(a, b, TAU32, tile=32, backend="interpret")
    w = p.work
    s = w.step_i.shape[0]
    steps = int(p.steps)  # one per k-block of p.kb k-tiles with work
    assert s >= steps and (s & (s - 1)) == 0  # power-of-two bucket
    flags = np.asarray(w.step_flags)
    assert np.all(flags[steps:] == 0)  # padding steps are inert
    # each pair opens with INIT and closes with FLUSH exactly once
    assert np.sum((flags & smm.STEP_INIT) != 0) == w.num_pairs
    assert np.sum((flags & smm.STEP_FLUSH) != 0) == w.num_pairs
    assert np.sum((flags & smm.STEP_ACC) != 0) == steps
    # and the steps' surviving k-tiles are the valid products
    _, real = pl._step_subtiles(w.step_k, w.step_flags, p.kb)
    assert int(np.sum(np.asarray(real))) == w.num_valid


@pytest.mark.parametrize("block_n", [1, 2])
def test_concrete_and_traced_flat_plans_gate_identically(block_n):
    """The concrete host gate (numpy products + nonzero scan) and the traced
    `gate_mask` are two renderings of ONE gating rule — lock them together
    so a future edit to either cannot silently diverge the plans."""
    a, b = _decay(96, 128, 40), _decay(128, 128, 41)
    p_eager = pl.plan(a, b, TAU32, tile=32, block_n=block_n,
                      backend="interpret")
    traced_mask = jax.jit(
        lambda a_, b_: pl.plan(a_, b_, TAU32, tile=32, block_n=block_n,
                               backend="interpret").mask
    )(a, b)
    np.testing.assert_array_equal(np.asarray(p_eager.mask),
                                  np.asarray(traced_mask))


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_hier_plan_with_concrete_operands_under_outer_jit(backend):
    """Under an enclosing jit, nested-jit kernels return tracers even for
    concrete operands — the planner must fall back to the traced gate
    instead of crashing in the host descent (regression)."""
    a, b = _decay(96, 96, 44), _decay(96, 96, 45)

    @jax.jit
    def frac(s):
        p = pl.plan(a, b, TAU32, tile=32, backend=backend, levels=2)
        return p.valid_fraction + s

    got = float(frac(0.0))
    want = float(pl.plan(a, b, TAU32, tile=32, backend=backend,
                         levels=2).valid_fraction)
    assert got == pytest.approx(want)


def test_reading_lazy_mask_keeps_plan_treedef_stable():
    """Materializing the derived mask must not change the plan's pytree
    structure — jit caches are keyed on it."""
    a, b = _decay(96, 96, 42), _decay(96, 96, 43)
    p = pl.plan(a, b, TAU32, tile=32, backend="interpret")
    td_before = jax.tree_util.tree_structure(p)
    _ = p.mask  # materialize the cache
    td_after = jax.tree_util.tree_structure(p)
    assert td_before == td_after


def test_worklist_plan_is_a_pytree_through_jit():
    a, b = _decay(96, 96, 13), _decay(96, 96, 14)
    p = pl.plan(a, b, TAU32, tile=32, backend="interpret")
    c1 = pl.execute(p, a, b)
    c2 = jax.jit(pl.execute)(p, a, b)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_plan_on_concrete_operands_never_sorts_dense_bitmap(monkeypatch):
    """Acceptance: the concrete planning path must not fall back to the
    O(gm·gn·gk log gk) dense-bitmap sort (`spamm_compact_ref`)."""
    calls = []
    real = ref.spamm_compact_ref
    monkeypatch.setattr(ref, "spamm_compact_ref",
                        lambda m: calls.append(1) or real(m))
    a, b = _decay(96, 96, 15), _decay(96, 96, 16)
    for levels in (0, 2):
        p = pl.plan(a, b, TAU32, tile=32, backend="interpret", levels=levels)
        pl.execute(p, a, b)
    assert not calls


# ---------------------------------------------------------------------------
# block_n padding regression (odd N) across the three entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_spamm_odd_n_block_n(backend):
    """N % (tile·block_n) != 0 used to trip the `gn % block_n` assert; the
    weight side now pads to tile·block_n and un-pads the output."""
    m, k, n = 96, 128, 160  # n/tile = 5 column tiles, block_n = 2 → ragged
    a, b = _decay(m, k, 20), _decay(k, n, 21)
    c, info = cs.spamm(a, b, TAU32, tile=32, block_n=2, backend=backend)
    assert c.shape == (m, n)
    # zero-padding must be invisible: same result as an explicitly padded
    # product, sliced back
    bp = pl.pad_to_tile(b, 32, 64)
    c_pad, _ = cs.spamm(a, bp, TAU32, tile=32, block_n=2, backend=backend)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_pad[:, :n]))
    # and the super-column mask is a superset of the fine mask: the result
    # must match the jnp masked-einsum oracle on the plan's own mask
    p = pl.plan(pl.pad_to_tile(a, 32), bp, TAU32, tile=32, block_n=2,
                backend="jnp")
    want = ops.get_backend("jnp").matmul(
        pl.pad_to_tile(a, 32), bp, p.mask, None, None, 32, 2, jnp.float32)
    np.testing.assert_allclose(np.asarray(c), np.asarray(want)[:m, :n],
                               atol=2e-4)


@pytest.mark.parametrize("use_ctx", [False, True])
def test_spamm_linear_odd_n_block_n(use_ctx):
    from repro.configs import SpammConfig

    x, w = _decay(80, 128, 22), _decay(128, 160, 23)
    ctx = None
    if use_ctx:
        ctx = mod.SpammContext(
            SpammConfig(enable=True, tau=TAU32, tile=32, backend="jnp",
                        block_n=2))
    y = mod.spamm_linear(x, w, jnp.float32(TAU32), 32, "jnp", "dense", 2,
                         ctx, 0)
    assert y.shape == (80, 160)
    y2, _ = cs.spamm(x, w, TAU32, tile=32, block_n=2, backend="jnp")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=2e-4)


def test_spamm_linear_odd_n_block_n_bwd_spamm():
    """The bwd="spamm" replan path pads g and w consistently with the
    forward's block_n-padded normmaps."""
    x, w = _decay(64, 96, 24), _decay(96, 160, 25)

    def loss(x_, w_):
        y = mod.spamm_linear(x_, w_, jnp.float32(TAU32), 32, "jnp", "spamm",
                             2, None, 0)
        return jnp.sum(y * y)

    dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert dx.shape == x.shape and dw.shape == w.shape
    assert np.all(np.isfinite(np.asarray(dx)))
    assert np.all(np.isfinite(np.asarray(dw)))


@pytest.mark.parametrize("shared_w", [True, False])
def test_spamm_bmm_odd_n_block_n(shared_w):
    bsz, m, k, n = 2, 64, 96, 160
    x = jnp.stack([_decay(m, k, 30 + i) for i in range(bsz)])
    if shared_w:
        w = _decay(k, n, 32)
    else:
        w = jnp.stack([_decay(k, n, 33 + i) for i in range(bsz)])
    c, info = pl.spamm_bmm(x, w, TAU32, tile=32, block_n=2, backend="jnp")
    assert c.shape == (bsz, m, n)
    for i in range(bsz):
        w_i = w if shared_w else w[i]
        want, _ = cs.spamm(x[i], w_i, TAU32, tile=32, block_n=2,
                           backend="jnp")
        np.testing.assert_allclose(np.asarray(c[i]), np.asarray(want),
                                   atol=2e-4)


# ---------------------------------------------------------------------------
# k-blocked work-lists: kb k-tiles a grid step
# ---------------------------------------------------------------------------

KB_GM, KB_GN, KB_GK, KB_T = 2, 3, 16, 32


def _kb_mask(kind):
    """(gm, gn, gk) gates at super-column granularity: every kind leaves
    some k-blocks partly filled or empty, the banded one also output pairs
    with no surviving k-tile at all."""
    shape = (KB_GM, KB_GN, KB_GK)
    if kind == "empty":
        return np.zeros(shape, bool)
    if kind == "full":
        return np.ones(shape, bool)
    if kind == "random":
        return np.random.default_rng(5).uniform(size=shape) < 0.1
    i, j, k = np.indices(shape)
    # a decay-like band around the diagonals of A (i, k) and B (k, j)
    return (np.abs(k - 8 * i - 4) <= 3) & (np.abs(k - 4 * j - 5) <= 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_n", [1, 2])
@pytest.mark.parametrize("kind", ["empty", "full", "random", "banded"])
@pytest.mark.parametrize("kb", [2, 4, 16])
def test_blocked_worklist_bit_identical_to_kb1(kb, kind, block_n, dtype):
    """The k-blocked kernel adds the same tile dots in the same ascending-k
    order as one tile a step, so C is bit for bit the kb = 1 kernel's, with
    bucket-padding steps (bucket_min) and unvisited output pairs."""
    mask = _kb_mask(kind)
    m, k = KB_GM * KB_T, KB_GK * KB_T
    n = KB_GN * KB_T * block_n
    rng = np.random.default_rng(kb)
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)

    def run(kb_):
        w, _ = pl.compact_from_triples(*np.nonzero(mask), gm=KB_GM, gn=KB_GN,
                                       gk=KB_GK, kb=kb_, bucket_min=64)
        steps = [jnp.asarray(x) for x in
                 (w.step_i, w.step_j, w.step_k, w.step_flags)]
        return w, smm.spamm_mm_worklist(a, b, *steps, tile=KB_T,
                                        block_n=block_n, kb=kb_,
                                        interpret=True)

    w1, c1 = run(1)
    wk, ck = run(kb)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(c1))
    # the k-blocked view names exactly the surviving k-tiles
    got = np.zeros_like(mask)
    k_, real = pl._step_subtiles(jnp.asarray(wk.step_k),
                                 jnp.asarray(wk.step_flags), kb)
    k_, real = np.asarray(k_), np.asarray(real)
    si, sj = np.asarray(wk.step_i), np.asarray(wk.step_j)
    for c in range(kb):
        got[si[real[:, c]], sj[real[:, c]], k_[real[:, c], c]] = True
    np.testing.assert_array_equal(got, mask)
    assert wk.step_i.shape[0] == 64  # padded to the bucket
    np.testing.assert_array_equal(wk.klist, w1.klist)  # pair view per tile


def _legacy_tables(ii, jj, kk, s):
    """One step per surviving triple, INIT/ACC/FLUSH only: the tables of the
    one-tile-a-step kernel, built independently of compact_from_triples."""
    order = np.lexsort((kk, jj, ii))
    ii, jj, kk = ii[order], jj[order], kk[order]
    v = ii.size
    flags = np.full(v, smm.STEP_ACC, np.int32)
    first = np.ones(v, bool)
    first[1:] = (ii[1:] != ii[:-1]) | (jj[1:] != jj[:-1])
    last = np.append(first[1:], True)
    flags[first] |= smm.STEP_INIT
    flags[last] |= smm.STEP_FLUSH
    pad = lambda x, fill: np.concatenate([x, np.full(s - v, fill)])
    return (pad(ii, ii[-1]), pad(jj, jj[-1]), pad(kk, kk[-1]), pad(flags, 0))


def test_kb_choice_kb1_tables_full_gate_and_vmem_bound():
    """kb = 1 reproduces the one-tile-a-step tables exactly; a full gate at
    tile 128 takes the widest k-block the VMEM budget admits; the budget is
    what bounds it, at f32 and at bf16."""
    from repro.core import cost
    from repro.kernels.common import VMEM_BUDGET, worklist_vmem_bytes

    mask = _kb_mask("banded")
    ii, jj, kk = np.nonzero(mask)
    w, _ = pl.compact_from_triples(ii, jj, kk, gm=KB_GM, gn=KB_GN, gk=KB_GK)
    s = w.step_i.shape[0]
    for got, want in zip((w.step_i, w.step_j, w.step_k, w.step_flags),
                         _legacy_tables(ii, jj, kk, s)):
        np.testing.assert_array_equal(np.asarray(got), want)
    # a plan that chooses kb = 1 carries those same tables
    coeffs = cost.DEFAULT_COEFFS["pallas"]
    free_steps = coeffs._replace(step_overhead_s=0.0)
    assert cost.choose_kb(ii, jj, kk, gk=KB_GK, tile=128, block_n=1,
                          dtype="float32", coeffs=free_steps) == 1

    gm, gn, gk = 2, 4, 64
    full = [x.ravel() for x in np.mgrid[0:gm, 0:gn, 0:gk]]
    for dtype, block_n, want in (("float32", 1, 16), ("float32", 4, 8),
                                 ("bfloat16", 4, 16), ("int8", 1, 1)):
        kb = cost.choose_kb(*full, gk=gk, tile=128, block_n=block_n,
                            dtype=dtype, coeffs=coeffs)
        assert kb == want, (dtype, block_n, kb)
        isize = 1 if dtype == "int8" else 2 if dtype == "bfloat16" else 4
        assert worklist_vmem_bytes(128, kb, block_n, isize) <= VMEM_BUDGET
        if kb < 16 and dtype != "int8":
            assert worklist_vmem_bytes(128, 2 * kb, block_n,
                                       isize) > VMEM_BUDGET
    # whole k-blocks only: 12 k-tiles admit 1, 2 and 4
    assert cost.kb_candidates(12, tile=128, block_n=1,
                              dtype="float32") == [1, 2, 4]
