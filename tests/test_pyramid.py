"""Norm-pyramid gating: the exactness invariant and its riders.

(a) pyramid level-l normmaps equal a direct get-norm pass at tile·2^l
    (within fp tolerance — the pyramid is ONE pass + cheap poolings);
(b) the hierarchical mask is bit-identical to flat `gate_mask` for random
    and banded-decay matrices on the jnp and interpret backends (eager
    sparse descent AND the traced dense refinement);
(c) the layers that ride on the pyramid: coarse-first τ-search, coarse
    work estimates / auto schedule, pyramid-caching WeightPlanCache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as pl
from repro.core import schedule
from repro.core import spamm as cs
from repro.core.tau_search import search_tau, search_tau_pyramid
from repro.kernels import ops, ref

BACKENDS = ("jnp", "interpret")


def _random(m, n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))


def _banded(n, seed, lam=0.6):
    return jnp.asarray(cs.exponential_decay(n, lam=lam, seed=seed))


# ---------------------------------------------------------------------------
# (a) pyramid levels == direct get-norm at the coarse tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,tile", [
    pytest.param("jnp", 32, id="jnp"),
    pytest.param("interpret", 32, id="interpret"),
    pytest.param("interpret", 128, id="interpret-tile128"),
])
def test_pyramid_levels_match_direct_tile_norms(backend, tile):
    """levels[l] must equal tile_norms at tile·2^l (dims chosen divisible so
    the direct pass exists), within fp tolerance."""
    levels = 2
    s = tile // 32
    # both sides sum (tile·2^l)² f32 squares in different orders; at tile
    # 128 the level-2 tiles hold 262144 of them (~sqrt(n)·2^-24 ≈ 3e-5)
    rtol = 1e-5 if tile == 32 else 5e-5
    for x in (_random(256 * s, 512 * s, 0), _banded(256 * s, 1)):
        pyr = ops.pyramid_norms(x, tile, levels, backend=backend)
        assert len(pyr) == levels + 1
        for l in range(levels + 1):
            want = ref.tile_norms_ref(x, tile * 2 ** l)
            np.testing.assert_allclose(
                np.asarray(pyr[l]), np.asarray(want), rtol=rtol, atol=1e-6)


@pytest.mark.parametrize("backend,tile", [
    pytest.param("jnp", 32, id="jnp"),
    pytest.param("interpret", 32, id="interpret"),
    pytest.param("interpret", 128, id="interpret-tile128"),
])
def test_pyramid_ragged_edges_zero_padded(backend, tile):
    """Odd grid dims: the coarse level pools a phantom zero row/col, so the
    surviving entries still match sqrt-sumsq of the real children."""
    x = _random(3 * tile, 5 * tile, 2)  # grids (3, 5) -> (2, 3) -> (1, 2)
    pyr = ops.pyramid_norms(x, tile, 2, backend=backend)
    assert pyr[0].shape == (3, 5)
    assert pyr[1].shape == (2, 3) and pyr[2].shape == (1, 2)
    np.testing.assert_allclose(
        np.asarray(pyr[1]), np.asarray(ref.pool_norms_ref(pyr[0])), rtol=1e-6)


@pytest.mark.parametrize("tile", [32, 128])
def test_pyramid_backend_parity(tile):
    """jnp and interpret (exact Pallas kernel body) pyramids agree."""
    x = _banded(6 * tile, 3)
    pj = ops.pyramid_norms(x, tile, 2, backend="jnp")
    pi = ops.pyramid_norms(x, tile, 2, backend="interpret")
    for a, b in zip(pj, pi):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_coarse_norm_upper_bounds_children():
    """The pruning lever: every coarse entry >= each descendant tile norm."""
    x = _random(256, 256, 4)
    pyr = pl.NormPyramid.build(x, 2, tile=32, backend="jnp")
    for l in range(1, 3):
        fine = np.asarray(pyr.levels[l - 1])
        coarse = np.asarray(pyr.levels[l])
        gm, gk = fine.shape
        up = np.repeat(np.repeat(coarse, 2, 0), 2, 1)[:gm, :gk]
        assert (up >= fine * (1 - 1e-6)).all()


# ---------------------------------------------------------------------------
# (b) the exactness invariant: hierarchical mask ≡ flat mask, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("levels", [1, 2, 3])
def test_hier_mask_bit_identical_random(backend, levels):
    a, b = _random(256, 256, 10), _random(256, 256, 11)
    na = ops.tile_norms(a, 32, backend=backend)
    nb = ops.tile_norms(b, 32, backend=backend)
    # τ exactly equal to a product value present in the tensor — the
    # boundary case where a sloppy coarse test would flip bits
    prods = np.asarray(na)[:, None, :] * np.asarray(nb).T[None]
    tau = float(np.median(prods))
    p0 = pl.plan(a, b, tau, tile=32, backend=backend)
    pL = pl.plan(a, b, tau, tile=32, backend=backend, levels=levels)
    assert 0.0 < float(p0.valid_fraction) < 1.0
    np.testing.assert_array_equal(np.asarray(p0.mask), np.asarray(pL.mask))
    np.testing.assert_array_equal(
        np.asarray(pl.execute(p0, a, b)), np.asarray(pl.execute(pL, a, b)))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("block_n", [1, 2])
def test_hier_mask_bit_identical_banded(backend, block_n):
    a, b = _banded(512, 20), _banded(512, 21)
    p0 = pl.plan(a, b, 0.02, tile=32, block_n=block_n, backend=backend)
    pL = pl.plan(a, b, 0.02, tile=32, block_n=block_n, backend=backend,
                 levels=3)
    assert 0.0 < float(p0.valid_fraction) < 1.0
    np.testing.assert_array_equal(np.asarray(p0.mask), np.asarray(pL.mask))
    assert pL.levels == 3 and p0.levels == 0


def test_hier_mask_traced_path_matches_eager():
    """The dense traced refinement (hier_gate_mask under jit) must equal
    both the eager sparse descent and flat gating; and plan(levels=...)
    under jit — which downgrades to flat, since the mask is identical and
    the descent can't run there — must agree too."""
    a, b = _banded(256, 22), _banded(256, 23)
    pyr_a = pl.NormPyramid.build(a, 2, tile=32, backend="jnp")
    pyr_b = pl.NormPyramid.build(b, 2, tile=32, backend="jnp")

    m_traced = np.asarray(
        jax.jit(pl.hier_gate_mask)(pyr_a, pyr_b, jnp.float32(0.02)))

    @jax.jit
    def traced_plan_mask(a_, b_):
        p = pl.plan(a_, b_, 0.02, tile=32, backend="jnp", levels=2)
        return p.mask

    m_plan_jit = np.asarray(traced_plan_mask(a, b))
    m_eager = np.asarray(
        pl.plan(a, b, 0.02, tile=32, backend="jnp", levels=2).mask)
    m_flat = np.asarray(pl.plan(a, b, 0.02, tile=32, backend="jnp").mask)
    np.testing.assert_array_equal(m_traced, m_eager)
    np.testing.assert_array_equal(m_plan_jit, m_eager)
    np.testing.assert_array_equal(m_traced, m_flat)


def test_search_tau_pyramid_explicit_tol():
    """tol passed explicitly reaches the jitted search as a tracer — must
    not crash (regression: Python max() on a traced tol)."""
    na = ref.tile_norms_ref(
        jnp.asarray(cs.algebraic_decay(256, c=0.1, lam=0.1, seed=28)), 32)
    pa = pl.NormPyramid.from_normmap(na, 2, tile=32)
    tau, res = search_tau_pyramid(pa, pa, 0.3, tol=0.005)
    # lands where the flat search lands with the same explicit tol
    _, res_f = search_tau(na, na, 0.3, tol=0.005)
    assert abs(float(res.achieved_ratio) -
               float(res_f.achieved_ratio)) < 0.03


def test_hier_plan_from_pyramid_operands():
    """plan() accepts NormPyramid operands directly (the cached-weight
    shape) and deepens a too-shallow pyramid instead of failing."""
    a, b = _banded(256, 24), _banded(256, 25)
    pyr_a = pl.NormPyramid.build(a, 2, tile=32, backend="jnp")
    pyr_b = pl.NormPyramid.build(b, 1, tile=32, backend="jnp")  # shallower
    p = pl.plan(None, None, 0.02, norm_a=pyr_a, norm_b=pyr_b, tile=32,
                backend="jnp")
    p0 = pl.plan(a, b, 0.02, tile=32, backend="jnp")
    np.testing.assert_array_equal(np.asarray(p.mask), np.asarray(p0.mask))
    assert p.levels == 2


def test_hier_fully_pruned_and_fully_dense():
    a, b = _banded(128, 26), _banded(128, 27)
    hi = pl.plan(a, b, 1e9, tile=32, backend="jnp", levels=2)
    assert int(hi.valid_tiles) == 0
    lo = pl.plan(a, b, 0.0, tile=32, backend="jnp", levels=2)
    assert int(lo.valid_tiles) == lo.total_tiles


# ---------------------------------------------------------------------------
# (c) riders: τ-search, schedule estimates, weight cache, spamm_bmm
# ---------------------------------------------------------------------------

def test_search_tau_pyramid_hits_target():
    n, tile = 512, 32
    a = cs.algebraic_decay(n, c=0.1, lam=0.1, seed=0)
    b = cs.algebraic_decay(n, c=0.1, lam=0.1, seed=1)
    na = ref.tile_norms_ref(jnp.asarray(a), tile)
    nb = ref.tile_norms_ref(jnp.asarray(b), tile)
    pa = pl.NormPyramid.from_normmap(na, 2, tile=tile)
    pb = pl.NormPyramid.from_normmap(nb, 2, tile=tile)
    for target in (0.3, 0.15, 0.05):
        tau_h, res_h = search_tau_pyramid(pa, pb, target)
        assert abs(float(res_h.achieved_ratio) - target) < 0.02
        # the flat search agrees on the achieved ratio at the found τ
        tau_f, res_f = search_tau(na, nb, target)
        assert abs(float(res_f.achieved_ratio) -
                   float(res_h.achieved_ratio)) < 0.03


def test_plan_valid_ratio_with_levels():
    a = jnp.asarray(cs.algebraic_decay(256, c=0.1, lam=0.1, seed=30))
    b = jnp.asarray(cs.algebraic_decay(256, c=0.1, lam=0.1, seed=31))
    p = pl.plan(a, b, valid_ratio=0.3, tile=32, backend="jnp", levels=2)
    assert 0.2 < float(p.valid_fraction) < 0.4
    # and on a nastier (step-quantized) banded input the hierarchical search
    # lands exactly where the flat search lands
    a2, b2 = _banded(256, 30), _banded(256, 31)
    pf = pl.plan(a2, b2, valid_ratio=0.3, tile=32, backend="jnp")
    ph = pl.plan(a2, b2, valid_ratio=0.3, tile=32, backend="jnp", levels=2)
    assert float(pf.valid_fraction) == pytest.approx(
        float(ph.valid_fraction), abs=0.05)


def test_v_matrix_accepts_pyramids_and_levels():
    a, b = _banded(512, 32), _banded(512, 33)
    pa = pl.NormPyramid.build(a, 2, tile=32, backend="jnp")
    pb = pl.NormPyramid.build(b, 2, tile=32, backend="jnp")
    v0 = schedule.v_matrix(pa, pb, 0.02, level=0)
    v_flat = schedule.v_matrix(pa.base, pb.base, 0.02)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v_flat))
    v2 = schedule.v_matrix(pa, pb, 0.02, level=2)
    assert v2.shape == (4, 4)  # 16×16 grid pooled twice
    # coarse estimate sees work where fine work exists
    assert int(jnp.sum(v2)) > 0
    # unequal depths clamp jointly to the shallower side (no shape crash)
    pb1 = pl.NormPyramid.build(b, 1, tile=32, backend="jnp")
    v1 = schedule.v_matrix(pa, pb1, 0.02, level=2)
    assert v1.shape == (8, 8)
    # one plain side forces the base level
    v_mixed = schedule.v_matrix(pa, pb.base, 0.02, level=2)
    np.testing.assert_array_equal(np.asarray(v_mixed), np.asarray(v0))


def test_auto_schedule_picks_cyclic_only_when_it_helps():
    g = 32
    skew = np.full((g, g), 1e-4, np.float32)
    skew[: g // 4] = 10.0  # top-heavy rows → contiguous strips imbalanced
    v_skew = schedule.v_matrix(
        jnp.asarray(skew), jnp.asarray(np.ones((g, g), np.float32)), 0.5)
    assert schedule.auto_schedule(v_skew, 4) == "cyclic"
    assert schedule.auto_schedule(jnp.ones((g, g), jnp.int32), 4) == \
        "contiguous"
    # fewer row groups than devices: nothing to reassign
    assert schedule.auto_schedule(jnp.ones((2, 2), jnp.int32), 4) == \
        "contiguous"


def test_coarse_loads_attributed_through_fine_shard_boundaries():
    """A coarse row straddling a fine shard boundary splits its work across
    the devices that own its fine rows; array_split over coarse rows gave it
    wholly to one side and could mis-pick the schedule."""
    # gm=18 fine rows, level=2 (4 fine rows per coarse row, ceil → 5 coarse
    # rows), 2 devices: the fine boundary at row 9 cuts coarse row 2 (fine
    # rows 8–11) 1:3. All work in that row:
    v = np.zeros((5, 5), np.int64)
    v[2, :] = 4
    v = jnp.asarray(v)
    contig = schedule.device_loads(v, 2, "contiguous", level=2, fine_rows=18)
    np.testing.assert_allclose(contig, [5.0, 15.0])
    cyc = schedule.device_loads(v, 2, "cyclic", level=2, fine_rows=18)
    np.testing.assert_allclose(cyc, [10.0, 10.0])
    # the coarse-row array_split saw [20, 0] for BOTH schedules (coarse
    # cyclic reshuffles whole coarse rows) and kept contiguous; the fine
    # attribution sees the real 1.5× imbalance that cyclic fixes
    assert schedule.auto_schedule(v, 2) == "contiguous"
    assert schedule.auto_schedule(v, 2, level=2, fine_rows=18) == "cyclic"


def test_fine_attribution_matches_flat_at_level_zero():
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.integers(0, 9, (16, 16)).astype(np.int32))
    for sched in ("contiguous", "cyclic"):
        loads = schedule.device_loads(v, 4, sched)
        want = [float(jnp.sum(jnp.sum(v, 1)[np.asarray(
            schedule.rows_for_device(d, 4, 16, sched))])) for d in range(4)]
        np.testing.assert_allclose(loads, want)
    assert schedule.auto_schedule(v, 4) == \
        schedule.auto_schedule(v, 4, fine_rows=16)


def test_weight_cache_holds_pyramid():
    w = _banded(256, 40)
    cache = pl.WeightPlanCache()
    wp1, nw1 = cache.weight_side(w, tile=32, backend="jnp", levels=2)
    wp2, nw2 = cache.weight_side(w, tile=32, backend="jnp", levels=2)
    assert cache.hits == 1 and cache.misses == 1
    assert isinstance(nw1, pl.NormPyramid) and nw1 is nw2
    assert nw1.num_levels == 2
    # different levels is a different cache entry, not a stale hit
    _, nw0 = cache.weight_side(w, tile=32, backend="jnp")
    assert cache.misses == 2 and not isinstance(nw0, pl.NormPyramid)
    np.testing.assert_array_equal(np.asarray(nw0), np.asarray(nw1.base))


def test_cached_hier_plan_matches_flat_result():
    x, w = _banded(192, 41), _banded(192, 42)
    cache = pl.WeightPlanCache()
    xp = pl.pad_to_tile(x, 32)
    p, wp = cache.plan_for(xp, w, 0.02, tile=32, backend="jnp", levels=2)
    got = pl.execute(p, xp, wp)[: x.shape[0], : w.shape[1]]
    want, _ = cs.spamm(x, w, 0.02, tile=32, backend="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend", BACKENDS)
def test_spamm_bmm_levels_matches_flat(backend):
    x = jnp.stack([_banded(96, 50 + i) for i in range(2)])[:, :, :64]
    w = _banded(96, 52)[:64, :]
    c0, i0 = pl.spamm_bmm(x, w, 0.02, tile=32, backend=backend)
    cL, iL = pl.spamm_bmm(x, w, 0.02, tile=32, backend=backend, levels=2)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(cL))
    assert float(i0.valid_fraction) == float(iL.valid_fraction)


def test_pyramid_is_a_pytree():
    pyr = pl.NormPyramid.build(_banded(128, 60), 2, tile=32, backend="jnp")
    leaves, treedef = jax.tree_util.tree_flatten(pyr)
    assert len(leaves) == 3
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.tile == pyr.tile and back.num_levels == 2

    @jax.jit
    def through_jit(p):
        return p.coarse

    np.testing.assert_allclose(np.asarray(through_jit(pyr)),
                               np.asarray(pyr.coarse), rtol=1e-6)
