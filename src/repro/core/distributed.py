"""Distributed SpAMM (paper §3.4 + §3.5.1, extended beyond the paper).

Paper-faithful mode (`spamm_rowpart`): C is row-partitioned across devices on
one mesh axis, B is replicated — the multi-GPU scheme of §3.4 (the paper
streams B/A in batches over PCIe; on a TPU pod the replication is an
all-gather the XLA scheduler overlaps with the local get-norm compute, which
plays the role of the paper's batched-UM transfer overlap).

Beyond-paper mode (`spamm_2d`): C sharded 2-D over (row_axis × col_axis); the
contraction dimension is sharded over col_axis, each device norm-gates its
local k-slice and the partial products are combined with a psum_scatter
(ring reduce-scatter, overlapped by XLA) — the SUMMA-style extension the
paper explicitly leaves as future work ("can be further integrated with
CANNON and SUMMA").

Row-strip schedules (both modes):

  'contiguous'  — uniform-width strips in storage order (paper §3.4
                  default). Cheapest HLO: no permutation, no gather.
  'cyclic'      — uniform-width strips of STRIDED tile-rows (paper §3.5.1
                  load balance). Balances smooth work profiles but pays an
                  in-step permutation collective ('pre_permuted' stores A
                  already permuted and is free).
  'equal_work'  — VARIABLE-width contiguous strips cut so each device's
                  predicted work (the coarse norm-pyramid V estimate) is
                  equal — `schedule.equal_work_partition`. No permutation
                  collective, handles skewed/banded/stride-aliased profiles
                  both uniform schedules lose on, and tolerates ragged
                  gm % num_devices != 0. The strip shapes are a per-device
                  row-offset table; pass a frozen table via `offsets=` to
                  skip the estimate (what the re-sharding controller does).

  'auto'        — per-call pick from the coarse work estimate
                  (`schedule.auto_schedule`): contiguous unless its
                  predicted imbalance exceeds the threshold AND cyclic
                  improves it; equal_work only when the uniform pick is
                  still imbalanced and the equal-work cut beats it by a
                  margin. Traced operands can't steer a Python-level
                  decision, so under jit 'auto' keeps the paper default
                  ('contiguous').

Drift/re-shard contract: a partition cut from one step's estimate may decay
as operands evolve. The control plane (`schedule.ReshardController`, driven
by the serving engine / train loop) re-probes the estimate every K steps and
re-cuts only when the live partition's predicted imbalance exceeds a fresh
cut's by the drift threshold; execution here is bit-identical under ANY
partition (gating and per-tile accumulation are row-independent), so
re-sharding never changes results — only where they are computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import plan as _plan
from repro.core import schedule as _schedule


def _work_estimate(a, b, tau, num_devices, *, tile, backend,
                   sched_levels: int):
    """Coarse work-estimate V for scheduling: (v, level, gm), or
    (None, 0, gm) when the operands are traced (jit) and no estimate can
    steer a Python-level decision.

    Builds norm pyramids for both operands and evaluates the §3.5.1 V matrix
    at the coarsest level that still gives every device ≥ 2 coarse rows (with
    exactly one, cyclic and contiguous assign identically and the estimate
    can't tell them apart) — the estimate costs one get-norm pass plus an
    8^level-reduced gating sweep, cheap enough to re-run per step as the
    operands evolve.
    """
    gm = a.shape[0] // tile
    if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
        return None, 0, gm
    lv = 0
    while lv < sched_levels and (gm >> (lv + 1)) >= 2 * num_devices:
        lv += 1
    pyr_a = _plan.NormPyramid.build(a, lv, tile=tile, backend=backend)
    pyr_b = _plan.NormPyramid.build(b, lv, tile=tile, backend=backend)
    return _schedule.v_matrix(pyr_a, pyr_b, tau, level=lv), lv, gm


def _pick_schedule(a, b, tau, num_devices, *, tile, backend,
                   sched_levels: int, offsets=None):
    """THE scheduling decision, shared by spamm_rowpart and spamm_2d:
    (schedule, offsets) given the operands and an optional frozen table.

    A supplied `offsets` table IS the decision (equal_work, no estimate).
    Otherwise "auto" picks contiguous/cyclic/equal_work from the coarse
    work estimate — device loads attributed through the FINE shard
    boundaries (`schedule.device_loads`), so a coarse row straddling a
    boundary splits its work across its actual owners instead of being
    array_split to one side — escalating to equal_work on ragged grids
    (uniform strips can't cover gm % ndev != 0), and cutting the offsets
    from the estimate already in hand (no second get-norm pass). Under jit
    the paper default ('contiguous') is kept.
    """
    gm = a.shape[0] // tile
    if offsets is not None:
        return "equal_work", offsets
    v, lv, _ = _work_estimate(a, b, tau, num_devices, tile=tile,
                              backend=backend, sched_levels=sched_levels)
    if v is None:
        return "contiguous", None  # traced: paper default
    schedule = _schedule.auto_schedule(v, num_devices, level=lv,
                                       fine_rows=gm)
    if schedule != "equal_work" and gm % num_devices != 0:
        schedule = "equal_work"
    if schedule == "equal_work":
        offsets = _schedule.equal_work_partition(v, num_devices, level=lv,
                                                 fine_rows=gm)
    return schedule, offsets


def _resolve_schedule(a, b, tau, num_devices, *, tile, backend,
                      sched_levels: int, allow_equal_work: bool = True) -> str:
    """The "auto" pick as a bare name (diagnostics/tests; the execution
    paths use `_pick_schedule`, which also cuts the offsets)."""
    v, lv, gm = _work_estimate(a, b, tau, num_devices, tile=tile,
                               backend=backend, sched_levels=sched_levels)
    if v is None:
        return "contiguous"
    return _schedule.auto_schedule(v, num_devices, level=lv, fine_rows=gm,
                                   allow_equal_work=allow_equal_work)


def _strip_tables(offsets, gm: int, num_devices: int):
    """Clamp-pad gather tables of a variable-width row partition — now the
    shared `schedule.strip_tables` (the serving engine shards its compiled
    steps from the SAME construction, so a pod's `spamm_rowpart` cut and the
    engine's can never disagree). Kept as an alias at the historical name."""
    return _schedule.strip_tables(offsets, gm, num_devices)


def _equal_work_offsets(a, b, tau, num_devices, *, tile, backend,
                        sched_levels, gm):
    """Cut equal-work strips from a fresh coarse estimate (eager-only)."""
    v, lv, _ = _work_estimate(a, b, tau, num_devices, tile=tile,
                              backend=backend, sched_levels=sched_levels)
    if v is None:
        raise ValueError(
            "schedule='equal_work' under jit needs a precomputed partition: "
            "pass offsets= (e.g. from schedule.equal_work_partition or a "
            "ReshardController) — traced operands cannot be estimated")
    return _schedule.equal_work_partition(v, num_devices, level=lv,
                                          fine_rows=gm)


def _local_spamm(a_loc, b, tau, tile, backend, block_n,
                 compute_dtype="float32"):
    # gating on the device-local shard: plans are built per shard (each
    # shard's normmap slice is its own) and executed in place — the same
    # single gating implementation (core.plan) as the flat call path.
    # compute_dtype != f32 reproduces the numerics of a LOW-PRECISION
    # REPLICATED B: quantization is a pure per-tile function of b, so every
    # shard quantizing its replica equals quantize-once-then-broadcast — the
    # wire payload of that broadcast is what distributed.compression's
    # compress_tiles/halo_wire_bytes account for.
    p = _plan.plan(a_loc, b, tau, tile=tile, backend=backend, block_n=block_n,
                   compute_dtype=compute_dtype)
    c = _plan.execute(p, a_loc, b)
    return c, p.valid_fraction.reshape(1)


def spamm_rowpart(
    a: jax.Array,
    b: jax.Array,
    tau,
    mesh: Mesh,
    *,
    axis: str = "data",
    tile: int = 64,
    backend: str = "auto",
    block_n: int = 1,
    schedule: str = "contiguous",
    sched_levels: int = 3,
    offsets=None,
    compute_dtype: str = "float32",
):
    """Paper §3.4: row-partition C over `axis`, B replicated.

    a: (M, K), b: (K, N); M divisible by tile. The uniform schedules need
    M/tile divisible by mesh.shape[axis]; 'equal_work' handles ragged grids
    (gm % ndev != 0) through its padded variable-width strips. A non-None
    `offsets` table always routes through the equal_work path, whatever
    `schedule` says — a frozen partition IS the scheduling decision.
    schedule: 'contiguous' (paper default), 'cyclic' (§3.5.1 load balance —
    NOTE: permutes tile-rows *inside the step*, which lowers to a large
    collective; production jobs should store A pre-permuted and pass
    'pre_permuted', which is free: identical HLO to contiguous with cyclic
    balance. See EXPERIMENTS.md §Perf c1), 'pre_permuted', 'equal_work'
    (variable-width contiguous strips cut to equalize the coarse work
    estimate; `offsets=` supplies a frozen row-offset table, e.g. from a
    `schedule.ReshardController`), or 'auto' (coarse norm-pyramid work
    estimate at ≤ `sched_levels` coarsening steps picks the schedule per
    call — see the module docstring for the decision rule).
    Returns (C, mean_valid_fraction). Under equal_work the mean weights
    each device's fraction by its REAL strip width (uniform strips reduce
    to the plain mean); clamp-pad rows can still nudge a device's own
    fraction toward its last row's density — telemetry-grade, the product
    itself is exact.
    compute_dtype (float32 | bfloat16 | int8) runs each shard's gated GEMM
    in low precision with the conservative widened-τ gate; the replicated B
    then only needs to cross the wire in the quantized format (see
    `repro.distributed.compression.compress_tiles` / `halo_wire_bytes`).
    """
    m, k = a.shape
    ndev = mesh.shape[axis]
    gm = m // tile
    if offsets is not None or schedule == "auto":
        schedule, offsets = _pick_schedule(a, b, tau, ndev, tile=tile,
                                           backend=backend,
                                           sched_levels=sched_levels,
                                           offsets=offsets)
    fn = jax.shard_map(
        functools.partial(
            _local_spamm, tau=tau, tile=tile, backend=backend,
            block_n=block_n, compute_dtype=compute_dtype,
        ),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(axis, None), P(axis)),
    )

    if schedule == "equal_work":
        if offsets is None:
            offsets = _equal_work_offsets(a, b, tau, ndev, tile=tile,
                                          backend=backend,
                                          sched_levels=sched_levels, gm=gm)
        perm, keep = _strip_tables(offsets, gm, ndev)
        a_x = a.reshape(gm, tile, k)[perm].reshape(-1, k)
        c_x, fracs = fn(a_x, b)
        c = c_x.reshape(len(perm), tile, -1)[np.flatnonzero(keep)]
        # weight each device's fraction by its real (unpadded) strip width
        w = np.diff(np.asarray(offsets, np.float64))
        w = jnp.asarray(w / w.sum(), jnp.float32)
        return c.reshape(m, -1), jnp.sum(fracs.reshape(-1) * w)

    assert gm % ndev == 0, (gm, ndev, "ragged grids need schedule='equal_work'")
    in_step_perm = schedule == "cyclic"
    if in_step_perm:
        perm = _schedule.device_permutation(ndev, gm, schedule)
        inv = np.argsort(perm)
        a = a.reshape(gm, tile, k)[perm].reshape(m, k)
    c, fracs = fn(a, b)
    if in_step_perm:
        c = c.reshape(gm, tile, -1)[inv].reshape(m, -1)
    return c, jnp.mean(fracs)


def _local_spamm_psum(a_loc, b_loc, tau, tile, backend, block_n, col_axis):
    # gate on LOCAL k-slice norms: global bitmap decomposes per k, so the
    # union over shards equals the flat single-device bitmap (exactness).
    p = _plan.plan(a_loc, b_loc, tau, tile=tile, backend=backend,
                   block_n=block_n)
    c_part = _plan.execute(p, a_loc, b_loc)
    # ring reduce-scatter of the partial products over the contraction axis;
    # scatter along N so C ends fully 2-D sharded.
    c = jax.lax.psum_scatter(c_part, col_axis, scatter_dimension=1, tiled=True)
    return c, p.valid_fraction.reshape(1, 1)


def spamm_2d(
    a: jax.Array,
    b: jax.Array,
    tau,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    tile: int = 64,
    backend: str = "auto",
    block_n: int = 1,
    schedule: str = "contiguous",
    sched_levels: int = 3,
    offsets=None,
):
    """Beyond-paper SUMMA-style 2-D SpAMM.

    A sharded (rows over row_axis, K over col_axis); B sharded (K over
    col_axis); C comes back sharded (rows over row_axis, cols over col_axis)
    via psum_scatter. Norm gating happens on local k-slices — exact.
    schedule='auto'/'equal_work'/`offsets=` behave as in `spamm_rowpart`
    (the row partition is what varies; the k/N sharding over col_axis is
    untouched, so only the row grid may be ragged).
    Returns (C, mean_valid_fraction).
    """
    m, k = a.shape
    row_axes = row_axis if isinstance(row_axis, tuple) else (row_axis,)
    nrow = 1
    for ax in row_axes:
        nrow *= mesh.shape[ax]
    ncol = mesh.shape[col_axis]
    gm = m // tile
    assert (k // tile) % ncol == 0, (k, tile, ncol)
    if offsets is not None or schedule == "auto":
        schedule, offsets = _pick_schedule(a, b, tau, nrow, tile=tile,
                                           backend=backend,
                                           sched_levels=sched_levels,
                                           offsets=offsets)
    fn = jax.shard_map(
        functools.partial(
            _local_spamm_psum,
            tau=tau,
            tile=tile,
            backend=backend,
            block_n=block_n,
            col_axis=col_axis,
        ),
        mesh=mesh,
        in_specs=(P(row_axes, col_axis), P(col_axis, None)),
        out_specs=(P(row_axes, col_axis), P(row_axes, col_axis)),
    )

    if schedule == "equal_work":
        if offsets is None:
            offsets = _equal_work_offsets(a, b, tau, nrow, tile=tile,
                                          backend=backend,
                                          sched_levels=sched_levels, gm=gm)
        perm, keep = _strip_tables(offsets, gm, nrow)
        a_x = a.reshape(gm, tile, k)[perm].reshape(-1, k)
        c_x, fracs = fn(a_x, b)
        c = c_x.reshape(len(perm), tile, -1)[np.flatnonzero(keep)]
        # weight each row-group's fraction by its real strip width (fracs
        # is (nrow, ncol): average the k-shards, then width-weight rows)
        w = np.diff(np.asarray(offsets, np.float64))
        w = jnp.asarray(w / w.sum(), jnp.float32)
        return c.reshape(m, -1), jnp.sum(
            jnp.mean(fracs.reshape(len(w), -1), axis=1) * w)

    assert gm % nrow == 0, (gm, nrow, "ragged grids need schedule='equal_work'")
    in_step_perm = schedule == "cyclic"
    if in_step_perm:
        perm = _schedule.device_permutation(nrow, gm, schedule)
        inv = np.argsort(perm)
        a = a.reshape(gm, tile, k)[perm].reshape(m, k)
    c, fracs = fn(a, b)
    if in_step_perm:
        c = c.reshape(gm, tile, -1)[inv].reshape(m, -1)
    return c, jnp.mean(fracs)
