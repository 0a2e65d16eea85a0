"""Compile the main path's Pallas kernels for one described TPU v5e chip.

Nothing runs here: the TPU compiler that ships with libtpu compiles for a
chip that is described, not attached, and refuses what the chip would
refuse (blocks off the (8, 128) tiling, SMEM or VMEM overflow) — the
failures interpret mode cannot show. Shapes are musicgen-large's serving
GEMMs: 2048 activation rows, K and N of 2048 and 8192, tile 128.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu at a time, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as P
from repro.kernels import getnorm, spamm_mm
from repro.kernels.common import SMEM_BYTES

TILE = 128
ROWS = 2048
KN = [(2048, 2048), (2048, 8192), (8192, 2048)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _step_tables(sharding, m, k, n):
    # the dense worst case: every (i, j, k) tile product survives
    s = (m // TILE) * (k // TILE) * (n // TILE)
    return [_spec(sharding, (s,), jnp.int32)] * 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,n", KN)
def test_spamm_mm_worklist_compiles(one_chip, k, n, dtype):
    _compile(lambda a, b, *t: spamm_mm.spamm_mm_worklist(a, b, *t, tile=TILE),
             _spec(one_chip, (ROWS, k), dtype), _spec(one_chip, (k, n), dtype),
             *_step_tables(one_chip, ROWS, k, n))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,n", KN[1:])
def test_blocked_worklist_compiles(one_chip, k, n, dtype):
    """16 k-tiles a step, the serving cells' choice at tau = 0: one step per
    (i, j) for K = 2048, four for K = 8192."""
    kb = 16
    s = (ROWS // TILE) * (k // (TILE * kb)) * (n // TILE)
    _compile(lambda a, b, *t: spamm_mm.spamm_mm_worklist(a, b, *t, tile=TILE,
                                                         kb=kb),
             _spec(one_chip, (ROWS, k), dtype), _spec(one_chip, (k, n), dtype),
             *[_spec(one_chip, (s,), jnp.int32)] * 4)


def test_vmem_oversized_block_raises_own_error(one_chip):
    """16 f32 k-tiles a step at block_n 4 need 11.3 MiB of VMEM, over the
    kernel's 8 MiB budget: the program says so before the compiler does."""
    with pytest.raises(ValueError, match="VMEM"):
        jax.jit(lambda a, b, *t: spamm_mm.spamm_mm_worklist(
            a, b, *t, tile=TILE, kb=16, block_n=4)).lower(
            _spec(one_chip, (ROWS, 2048), jnp.float32),
            _spec(one_chip, (2048, 8192), jnp.float32),
            *[_spec(one_chip, (256,), jnp.int32)] * 4)


@pytest.mark.parametrize("k,n", KN)
def test_spamm_mm_worklist_int8_compiles(one_chip, k, n):
    f32 = jnp.float32
    _compile(
        lambda a, b, sa, sb, *t: spamm_mm.spamm_mm_worklist_int8(
            a, b, sa, sb, *t, tile=TILE),
        _spec(one_chip, (ROWS, k), jnp.int8), _spec(one_chip, (k, n), jnp.int8),
        _spec(one_chip, (ROWS // TILE, k // TILE), f32),
        _spec(one_chip, (k // TILE, n // TILE), f32),
        *_step_tables(one_chip, ROWS, k, n))


@pytest.mark.parametrize("use_mxu", [False, True])
@pytest.mark.parametrize("k", [2048, 8192])
def test_tile_norms_compiles(one_chip, k, use_mxu):
    _compile(lambda x: getnorm.tile_norms(x, TILE, use_mxu=use_mxu),
             _spec(one_chip, (ROWS, k), jnp.float32))


@pytest.mark.parametrize("k", [2048, 8192])
def test_tile_norms_quant_compiles(one_chip, k):
    _compile(lambda x: getnorm.tile_norms_quant(x, TILE),
             _spec(one_chip, (ROWS, k), jnp.float32))


@pytest.mark.parametrize("grid", [(16, 16), (16, 64), (64, 16), (3, 5)])
def test_pool_norms_compiles(one_chip, grid):
    _compile(getnorm.pool_norms, _spec(one_chip, grid, jnp.float32))


def test_tile_below_128_raises_own_error(one_chip):
    """Tile 64 on the compiled kernels: the program's ValueError, raised
    while tracing, before the TPU compiler is reached."""
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(lambda x: getnorm.tile_norms(x, 64)).lower(
            _spec(one_chip, (ROWS, 2048), jnp.float32))
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(lambda a, b, *t: spamm_mm.spamm_mm_worklist(
            a, b, *t, tile=64)).lower(
            _spec(one_chip, (ROWS, 2048), jnp.float32),
            _spec(one_chip, (2048, 2048), jnp.float32),
            *[_spec(one_chip, (4096,), jnp.int32)] * 4)


def test_pallas_plan_with_tile_64_raises():
    """The pallas backend refuses tile 64 when the plan is built — no
    fallback to another backend."""
    a = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        P.plan(a, a, 0.0, tile=64, backend="pallas")


def test_smem_oversized_worklist_raises_own_error(one_chip):
    """8192³ at tile 128 keeps up to 64³ tile products: four step tables of
    4 MiB, beyond SMEM. The program says so before the compiler does."""
    n = 8192
    s = (n // TILE) ** 3
    assert 4 * 4 * s > SMEM_BYTES
    with pytest.raises(ValueError, match="SMEM"):
        jax.jit(lambda a, b, *t: spamm_mm.spamm_mm_worklist(
            a, b, *t, tile=TILE)).lower(
            _spec(one_chip, (n, n), jnp.float32),
            _spec(one_chip, (n, n), jnp.float32),
            *[_spec(one_chip, (s,), jnp.int32)] * 4)


def test_largest_smem_worklist_compiles(one_chip):
    """The largest power-of-two work-list (what the frozen plans' bucketing
    produces) that fits SMEM compiles: 32768 steps, 512 KiB of tables."""
    n = 4096
    s = (n // TILE) ** 3
    assert s == 32768
    _compile(lambda a, b, *t: spamm_mm.spamm_mm_worklist(a, b, *t, tile=TILE),
             _spec(one_chip, (n, n), jnp.float32),
             _spec(one_chip, (n, n), jnp.float32),
             *[_spec(one_chip, (s,), jnp.int32)] * 4)
    np.testing.assert_array_less(4 * 4 * s, SMEM_BYTES)


@pytest.mark.parametrize("rows,k,n,kb", [
    (16384, 2048, 1408, 16),   # a prefill chunk's w1/w3: 128 row tiles
    (16384, 1408, 2048, 1),    # its w2: 11 k-tiles admit kb 1 only
    (1024, 2048, 1408, 16),    # a decode step: one row tile per expert
])
def test_expert_worklist_compiles(one_chip, rows, k, n, kb):
    """The expert layer's work-list kernel at moonlight-16b-a3b's widths,
    8 held experts stacked under B, one step per k-block of every row
    tile."""
    s = (rows // TILE) * (n // TILE) * (k // (TILE * kb))
    _compile(lambda a, b, si, sj, sk, sb, fl:
             spamm_mm.spamm_mm_worklist_moe(a, b, si, sj, sk, sb, fl,
                                            tile=TILE, kb=kb),
             _spec(one_chip, (rows, k), jnp.float32),
             _spec(one_chip, (8 * k, n), jnp.float32),
             *[_spec(one_chip, (s,), jnp.int32)] * 5)
