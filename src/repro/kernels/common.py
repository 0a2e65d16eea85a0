"""What the Pallas kernels share: the TPU compiler's limits, checked up
front, and the mesh axes a value varies over inside `shard_map` (kernel
outputs vary over their inputs' axes, so every kernel runs there too).

Interpret mode runs any tile and any table size; the chip's compiler does
not. Two limits bind the compiled kernels, and each is checked here before
tracing so the caller gets the program's own error instead of a Mosaic
refusal deep inside a jitted step:

* block layout: a block's last two dims must be multiples of (8, 128) or
  span the whole array. The kernels use square (tile, tile) blocks, so a
  compiled tile must be a multiple of the 128-wide lane dimension.
* scalar-prefetch tables (the work-list step tables, the dense-grid `kidx`
  table, the int8 scale tables) live in SMEM, which holds 1 MiB on v5e. At
  8192³ and tile 128 the work-list's four step tables alone need 4 MiB;
  splitting such a work-list across several kernel calls is a ROADMAP item.
* VMEM: the work-list kernel's blocks grow with the k-tiles one grid step
  covers (`kb`). Its double-buffered blocks plus the f32 accumulator are
  held to half of v5e's default scoped VMEM (16 MiB), so the planners'
  choice of `kb` and the kernel agree on what fits.
"""
from __future__ import annotations

import jax

LANE = 128
SMEM_BYTES = 1 << 20
# SMEM the compiler keeps for itself. A conservative bound, not the exact
# boundary: 4 step tables of 65536 entries (exactly 1 MiB) were refused,
# short by 1.1 KiB, and 63488 entries (32 KiB under 1 MiB) compiled; no
# size between the two was tried, so tables in that gap are refused here
SMEM_RESERVE = 32 << 10
# half of v5e's 16 MiB default scoped VMEM: room for the compiler's own
# buffers beside the work-list kernel's pipeline
VMEM_BUDGET = 8 << 20


def check_tile(tile: int) -> None:
    """Raise unless `tile` gives the compiled kernels legal blocks."""
    if tile % LANE:
        raise ValueError(
            f"the compiled Pallas kernels need a tile that is a multiple of "
            f"{LANE} (their (tile, tile) blocks sit on the {LANE}-wide lane "
            f"dimension); got tile={tile}. Use tile={LANE} on the pallas "
            f"backend, or the interpret/jnp backends for smaller tiles")


def check_smem(name: str, *tables) -> None:
    """Raise if the scalar-prefetch `tables` of kernel `name` overflow SMEM."""
    nbytes = sum(int(t.size) * t.dtype.itemsize for t in tables)
    if nbytes > SMEM_BYTES - SMEM_RESERVE:
        raise ValueError(
            f"{name}: its scalar-prefetch tables need {nbytes} bytes of SMEM "
            f"but a chip holds {SMEM_BYTES} ({SMEM_RESERVE} kept for the "
            f"compiler); this product has too many "
            f"surviving tile products for one kernel call (raise tau or the "
            f"tile, or split the operands)")


def worklist_vmem_bytes(tile: int, kb: int, block_n: int, in_itemsize: int,
                        out_itemsize: int = 4) -> int:
    """VMEM the work-list kernel holds at one k-block width `kb`: the
    double-buffered A (tile, kb·tile) and B (kb·tile, tile·block_n) blocks,
    the double-buffered zero-seed and output blocks, and the f32
    accumulator."""
    tn = tile * block_n
    a = tile * kb * tile * in_itemsize
    b = kb * tile * tn * in_itemsize
    seed_out = 2 * tile * tn * out_itemsize
    return 2 * (a + b + seed_out) + tile * tn * 4


def check_vmem(name: str, nbytes: int) -> None:
    """Raise if a kernel's blocks need more than `VMEM_BUDGET`."""
    if nbytes > VMEM_BUDGET:
        raise ValueError(
            f"{name}: its blocks need {nbytes} bytes of VMEM, over the "
            f"{VMEM_BUDGET}-byte budget (half of a v5e core's default scoped "
            f"VMEM); cover fewer k-tiles per grid step or a narrower block_n")


def mesh_vma(*xs) -> frozenset:
    """The manual mesh axes any of `xs` varies over (empty outside
    `shard_map`)."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def varying(x, vma: frozenset):
    """`x` marked as varying over `vma`: a constant seed (an aliased zero
    output buffer, a scan carry) must vary over the same axes as what it
    seeds."""
    return jax.lax.pcast(x, tuple(sorted(vma)), to="varying") if vma else x
