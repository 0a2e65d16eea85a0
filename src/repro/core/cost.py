"""Roofline-calibrated analytic cost model + kernel autotuner.

The paper's speedup hinges on blocking parameters that match the memory
hierarchy, yet the pipeline hardcodes `block_n`, pyramid `levels` and the
worklist bucket floor. This module makes parameter choice an explicit
bytes/flops computation per kernel, calibrated per machine:

  * **Counts** (`predict_counts`, `gemm_bytes`, `gemm_flops`) — the analytic
    per-kernel work of one SpAMM call: surviving work-list steps × tile
    footprints for `spamm_mm_worklist`/`_int8` (dtype itemsize-aware — the
    same formula as `SpammPlan.bytes_moved()`, which delegates here), the
    activation get-norm read, pyramid pooling reads, and the gate-product
    evaluations of flat vs hierarchical planning (a host simulation of the
    coarse-to-fine descent, counting candidates per level).
  * **Coefficients** (`CostCoeffs`, `CostProfile`, `calibrate`) — machine
    numbers that turn counts into seconds: sustained bytes/s, dot flops/s,
    per-grid-step launch overhead, per-call base overhead and host gate-op
    rate. `calibrate` fits them from measured wall-clock of the real
    kernels (`benchmarks/kernels_micro.py`-style timings: get-norm sweeps +
    work-list executes across τ) by non-negative least squares, and
    `CostProfile` persists them as JSON keyed by backend × device kind.
  * **Tuner** (`tune`, `tune_weight`) — per-weight argmin of predicted call
    time over `block_n` × pyramid `levels` × bucket floor. The hardcoded
    defaults are always in the search space, so the tuned pick is never
    predicted slower than them. The result is a `TunedParams` record that
    `FrozenWeight` carries as an aux field (persisted through `PlanStore`),
    so tuning amortizes exactly like the rest of the frozen-plan runtime.

Nothing here imports `core.plan` at module level (plan imports this module
for `bucket`/`gemm_bytes`); the calibration pass imports it lazily.
"""
from __future__ import annotations

import json
import os
import socket
import time
from typing import Mapping, NamedTuple, Optional, Sequence

import numpy as np

from repro.kernels import quantize as kquant

COST_SCHEMA_VERSION = 1

# representative activation row-tile grid the offline tuner prices calls at
# when the caller has no real shape in hand (precompute time: serving row
# grids are not known yet). Documented, deterministic — NOT a fit parameter.
DEFAULT_TUNE_GM = 8


def bucket(n: int, minimum: int = 16) -> int:
    """Pad a step count to a power-of-two bucket of at least `minimum` so
    the jitted ragged kernel compiles once per bucket, not once per distinct
    Σnvalid. THE bucket function — `core.plan._bucket` and
    `FrozenWeight.for_rows` both resolve through it; the tuner searches
    over `minimum` (the worklist bucket floor)."""
    return max(minimum, 1 << max(n - 1, 0).bit_length())


def bucket_ladder(n_max: int, minimum: int = 16) -> list:
    """Every bucket `bucket(n, minimum)` can return for n in [1, n_max] —
    the power-of-two ladder from `bucket(1)` up to `bucket(n_max)`. Its
    LENGTH is the compile-count bound for shape-bucketed serving: a sweep
    over arbitrary batch/slot counts ≤ n_max compiles at most
    `len(bucket_ladder(n_max, m))` distinct bucketed shapes (the serving
    engine's `trace_counts` guard asserts against exactly this)."""
    lo = bucket(1, minimum)
    hi = bucket(max(int(n_max), 1), minimum)
    out = [lo]
    while out[-1] < hi:
        out.append(out[-1] * 2)
    return out


# ---------------------------------------------------------------------------
# machine coefficients
# ---------------------------------------------------------------------------

class CostCoeffs(NamedTuple):
    """Per-(backend × device kind) machine coefficients, all in base SI
    units (bytes/s, flops/s, seconds). `calibrated` is False on the nominal
    fallback table below — the tuner still works (deterministically) but
    predictions are order-of-magnitude, not fitted."""
    bytes_per_s: float       # sustained memory bandwidth of the kernels
    flops_per_s: float       # sustained dot throughput (MXU / XLA dot)
    step_overhead_s: float   # per work-list grid step dispatch overhead
    base_overhead_s: float   # fixed per-call overhead (launch + Python)
    gate_ops_per_s: float    # host gate-product evaluations per second
    calibrated: bool = False


# Nominal fallbacks per backend when no calibration profile is attached.
# interpret's per-step overhead dominates everything (the kernel body runs
# step-by-step under emulation); pallas numbers are v5e-litepod-ish; jnp is
# a single fused XLA CPU einsum. Calibration replaces these.
DEFAULT_COEFFS = {
    "pallas": CostCoeffs(8.0e11, 2.0e14, 2.0e-7, 5.0e-6, 1.0e10),
    "interpret": CostCoeffs(2.0e9, 1.0e10, 4.0e-5, 3.0e-4, 2.0e8),
    "jnp": CostCoeffs(2.0e10, 5.0e10, 5.0e-7, 5.0e-5, 2.0e8),
}


def device_kind() -> str:
    """Kind string of device 0 ("cpu", "TPU v5 lite", ...). A missing
    backend raises: a profile key must never name a device that was not
    there."""
    import jax

    d = jax.devices()[0]
    return str(d.device_kind or d.platform)


def profile_key(backend: str, kind: Optional[str] = None) -> str:
    return f"{backend}/{kind if kind is not None else device_kind()}"


class CostProfile:
    """Calibrated coefficients keyed by backend × device kind, persisted as
    JSON (`{"schema": 1, "entries": {"interpret/cpu": {...}}, "meta": ...}`).

    `coeffs(backend)` falls back to the nominal `DEFAULT_COEFFS` table when
    the exact key is missing, then to any entry of the same backend — a
    profile calibrated on one host still beats nominals on a sibling."""

    def __init__(self, entries: Optional[dict] = None, meta: Optional[dict] = None):
        self.entries: dict = dict(entries or {})
        self.meta = dict(meta or {})

    def put(self, backend: str, coeffs: CostCoeffs, kind: Optional[str] = None):
        self.entries[profile_key(backend, kind)] = coeffs

    def coeffs(self, backend: str, kind: Optional[str] = None) -> CostCoeffs:
        key = profile_key(backend, kind)
        hit = self.entries.get(key)
        if hit is not None:
            return hit
        prefix = backend + "/"
        for k in sorted(self.entries):
            if k.startswith(prefix):
                return self.entries[k]
        return DEFAULT_COEFFS.get(backend, DEFAULT_COEFFS["jnp"])

    def key_used(self, backend: str, kind: Optional[str] = None) -> str:
        """The profile key `coeffs` resolves (for provenance in TunedParams)."""
        key = profile_key(backend, kind)
        if key in self.entries:
            return key
        prefix = backend + "/"
        for k in sorted(self.entries):
            if k.startswith(prefix):
                return k
        return f"{backend}/<nominal>"

    def save(self, path: str) -> str:
        payload = {
            "schema": COST_SCHEMA_VERSION,
            "entries": {k: v._asdict() for k, v in self.entries.items()},
            "meta": {**self.meta, "hostname": socket.gethostname()},
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "CostProfile":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("schema") != COST_SCHEMA_VERSION:
            raise ValueError(
                f"cost profile {path!r} has schema "
                f"{payload.get('schema')!r}; this build reads "
                f"{COST_SCHEMA_VERSION} — re-run calibration")
        entries = {k: CostCoeffs(**v) for k, v in payload["entries"].items()}
        return cls(entries, payload.get("meta"))

    @classmethod
    def load_or_default(cls, path: Optional[str]) -> "CostProfile":
        """A profile from `path`, or the empty (nominal-fallback) profile
        when path is None/missing — the tuner stays usable and
        deterministic without a calibration run."""
        if path and os.path.isfile(path):
            return cls.load(path)
        return cls()


# ---------------------------------------------------------------------------
# analytic per-kernel counts
# ---------------------------------------------------------------------------

def gemm_bytes(valid_tiles, pairs, tile: int, block_n: int, dtype):
    """GEMM bytes the executed work-list moves — per real step one
    (tile, tile) A block and one (tile, tile·block_n) B block at the
    compute dtype's itemsize, plus one f32 (tile, tile·block_n) output
    flush per active output pair. `SpammPlan.bytes_moved()` delegates here;
    accepts python floats or jnp arrays (pure arithmetic)."""
    isize = kquant.dtype_itemsize(dtype)
    t2 = float(tile * tile)
    return (valid_tiles * (t2 * (1 + block_n) * isize)
            + pairs * (t2 * block_n * 4.0))


def gemm_flops(valid_tiles, tile: int, block_n: int):
    """MXU flops of the executed work-list: one
    (tile, tile) @ (tile, tile·block_n) dot per real step."""
    return valid_tiles * (2.0 * tile * tile * tile * block_n)


class KernelCounts(NamedTuple):
    """Analytic work of ONE SpAMM call at a given parameterization."""
    steps_real: int          # accumulating work-list steps (Σnvalid)
    steps_grid: int          # grid length the kernel actually runs
    pairs: int               # active output (i, j) pairs (flush writes)
    gemm_bytes: float        # work-list operand reads + output flushes
    flops: float             # MXU dot flops over the real steps
    norm_bytes: float        # activation get-norm read (+ pooling reads)
    gate_ops: float          # planner gate-product evaluations


def _pool_norms_np(n: np.ndarray) -> np.ndarray:
    """Numpy twin of `kernels.ref.pool_norms_ref`: sqrt-sumsq 2×2 pooling
    with zero padding at ragged edges (host-side, for count simulation)."""
    gm, gk = n.shape
    pm, pk = gm % 2, gk % 2
    if pm or pk:
        n = np.pad(n, ((0, pm), (0, pk)))
    sq = n.astype(np.float64) ** 2
    pooled = (sq[0::2, 0::2] + sq[1::2, 0::2] + sq[0::2, 1::2]
              + sq[1::2, 1::2])
    return np.sqrt(pooled)


def _descent_gate_ops(na: np.ndarray, nb: np.ndarray, tau: float,
                      levels: int) -> float:
    """Gate-product evaluations of hierarchical planning at `levels`
    coarsening steps: the full coarsest grid plus 8× the survivors of every
    refinement level (levels=0 ⇒ the flat gate's full fine grid). Mirrors
    `core.plan._hier_descend_host`'s work, counting instead of collecting."""
    la, lb = [na], [nb]
    for _ in range(levels):
        la.append(_pool_norms_np(la[-1]))
        lb.append(_pool_norms_np(lb[-1]))
    top = levels
    gm_t, gk_t = la[top].shape
    gn_t = lb[top].shape[1]
    ops = float(gm_t) * gk_t * gn_t
    if levels == 0:
        return ops
    cand = (la[top][:, None, :] * np.swapaxes(lb[top], 0, 1)[None]
            >= tau)
    surv = float(cand.sum())
    for l in range(top - 1, -1, -1):
        ops += 8.0 * surv
        if surv == 0:
            break
        # refine the actual candidate set so per-level survivor counts are
        # exact, not a geometric guess
        gm_l, gk_l = la[l].shape
        gn_l = lb[l].shape[1]
        cand = np.repeat(np.repeat(np.repeat(cand, 2, 0), 2, 1), 2, 2)
        cand = cand[:gm_l, :gn_l, :gk_l]
        cand = cand & (la[l][:, None, :] * np.swapaxes(lb[l], 0, 1)[None]
                       >= tau)
        surv = float(cand.sum())
    return ops


def predict_counts(
    norm_a: np.ndarray,
    norm_b: np.ndarray,
    tau: float,
    *,
    tile: int,
    block_n: int = 1,
    dtype: str = "float32",
    levels: int = 0,
    bucket_min: int = 16,
    mode: str = "eager",
) -> KernelCounts:
    """Analytic call counts for (gm, gk) × (gk, gn) normmaps gated at `tau`.

    The gate here IS `core.plan.gate_mask`'s (any-member super-column
    grouping ≡ max-norm test, fp32 multiply monotone), so on the real
    normmaps the predicted steps/pairs equal the built plan's
    `valid_tiles`/active pairs exactly — the invariant
    `tests/test_cost_model.py` pins against `SpammPlan.bytes_moved()`.

    mode="eager": the grid runs exactly the surviving steps (bucket-padded).
    mode="frozen": the grid enumerates ALL weight-admissible steps
    (gm × pairs-with-nonzero-weight-norm, the `FrozenWeight.for_rows`
    tables) and the traced activation gate turns accumulation on per step —
    step overhead scales with the frozen table, bytes/flops with the
    surviving set. N is zero-padded up to tile·block_n like `pad_to_tile`.
    """
    na = np.asarray(norm_a, np.float64)
    nb = np.asarray(norm_b, np.float64)
    gm, gk = na.shape
    gn = nb.shape[1]
    pad_n = (-gn) % block_n
    if pad_n:
        nb = np.pad(nb, ((0, 0), (0, pad_n)))
        gn += pad_n
    gnb = gn // block_n
    nbmax = nb.reshape(gk, gnb, block_n).max(2) if block_n > 1 else nb
    mask = na[:, None, :] * np.swapaxes(nbmax, 0, 1)[None] >= tau
    v = int(mask.sum())
    pairs = int(mask.any(-1).sum())
    if mode == "frozen":
        if tau > 0.0:
            adm = int((nbmax > 0.0).sum())
        else:
            adm = gk * gnb
        steps_grid = bucket(gm * adm, bucket_min)
    elif mode == "eager":
        steps_grid = bucket(v, bucket_min)
    else:
        raise ValueError(f"mode {mode!r} not in ('eager', 'frozen')")
    norm_bytes = float(gm * tile) * (gk * tile) * 4.0
    lv_bytes, lvl = 0.0, (gm, gk)
    for _ in range(levels):
        lv_bytes += lvl[0] * lvl[1] * 4.0
        lvl = ((lvl[0] + 1) // 2, (lvl[1] + 1) // 2)
    gate_ops = (0.0 if mode == "frozen" else
                _descent_gate_ops(na, nb, tau, levels))
    if mode == "frozen":
        # the traced activation gate is one product-compare per grid step
        gate_ops = float(steps_grid)
    return KernelCounts(
        steps_real=v,
        steps_grid=steps_grid,
        pairs=pairs,
        gemm_bytes=float(gemm_bytes(float(v), float(pairs), tile, block_n,
                                    dtype)),
        flops=float(gemm_flops(float(v), tile, block_n)),
        norm_bytes=norm_bytes + lv_bytes,
        gate_ops=gate_ops,
    )


def predict_time_s(counts: KernelCounts, coeffs: CostCoeffs) -> float:
    """Roofline-style additive model: fixed call overhead + per-step
    dispatch + memory time + compute time + planner gate time. Additive
    (not max-of-terms) because the measured kernels overlap none of these
    phases — calibration fits the same decomposition."""
    return (coeffs.base_overhead_s
            + counts.steps_grid * coeffs.step_overhead_s
            + (counts.gemm_bytes + counts.norm_bytes) / coeffs.bytes_per_s
            + counts.flops / coeffs.flops_per_s
            + counts.gate_ops / coeffs.gate_ops_per_s)


def predict_plan_time_s(plan, coeffs: CostCoeffs):
    """Predicted wall-clock of ONE executed work-list call, computed from a
    (possibly traced) `SpammPlan`'s own fields — the in-trace twin of
    `predict_counts` (frozen mode) → `predict_time_s`.

    Pure jnp-compatible arithmetic: `valid_tiles`/`bytes_moved()` may be
    tracers, so the prediction embeds into the compiled step right next to
    the gate and prices the work-list that EXECUTION actually ran (not a
    planning-time estimate). The cost-residual telemetry taps this value per
    gated GEMM and pairs the per-phase sum with measured wall-clock — the
    feedback loop that surfaces a stale `CostProfile`."""
    gm, gk = plan.norm_a.shape
    if plan.work is not None and plan.work.step_i is not None:
        # frozen/work-list plans: the grid length is the static step-table
        # shape; one traced gate product-compare per grid step
        steps_grid = float(plan.work.step_i.shape[0])
    else:
        # dense-bitmap plans have no static grid; approximate with the
        # (possibly traced) surviving-step count
        steps_grid = plan.valid_tiles * 1.0
    gate_ops = steps_grid
    norm_bytes = float(gm * plan.tile) * (gk * plan.tile) * 4.0
    lv_bytes, lvl = 0.0, (gm, gk)
    for _ in range(plan.levels):
        lv_bytes += lvl[0] * lvl[1] * 4.0
        lvl = ((lvl[0] + 1) // 2, (lvl[1] + 1) // 2)
    flops = gemm_flops(plan.valid_tiles * 1.0, plan.tile, plan.block_n)
    return (coeffs.base_overhead_s
            + steps_grid * coeffs.step_overhead_s
            + (plan.bytes_moved() + norm_bytes + lv_bytes) / coeffs.bytes_per_s
            + flops / coeffs.flops_per_s
            + gate_ops / coeffs.gate_ops_per_s)


def predict_plan_static(plan, coeffs: CostCoeffs):
    """Split `predict_plan_time_s` into its STATIC part, evaluated on host
    at trace time — the zero-graph-cost path the telemetry taps use.

    Every term of the per-call prediction except the executed-work terms is
    a pure function of static plan metadata (normmap shapes, step-table
    length, levels, coefficients): base + step overheads, norm/pyramid
    bytes, gate ops. The two traced quantities — GEMM bytes and valid
    tiles — already leave the compiled step as its gating stats (bytes
    directly; valid tiles as valid_fraction × the static total_tiles), so
    the host finishes the prediction with `finish_plan_time_s` and the
    armed graph stays IDENTICAL to the unarmed one.

    Returns `(const_s, total_tiles, tile, block_n)` host floats, or None
    for plans without static step tables (no frozen work-list — the
    in-trace `predict_plan_time_s` still covers those if a caller wants
    the traced prediction)."""
    if plan.work is None or plan.work.step_i is None:
        return None
    gm, gk = plan.norm_a.shape
    steps_grid = float(plan.work.step_i.shape[0])
    norm_bytes = float(gm * plan.tile) * (gk * plan.tile) * 4.0
    lv_bytes, lvl = 0.0, (gm, gk)
    for _ in range(plan.levels):
        lv_bytes += lvl[0] * lvl[1] * 4.0
        lvl = ((lvl[0] + 1) // 2, (lvl[1] + 1) // 2)
    gmm, gnb, gkk = plan.grid
    const_s = (coeffs.base_overhead_s
               + steps_grid * coeffs.step_overhead_s
               + (norm_bytes + lv_bytes) / coeffs.bytes_per_s
               + steps_grid / coeffs.gate_ops_per_s)
    return (const_s, float(gmm * gnb * gkk), plan.tile, plan.block_n)


def finish_plan_time_s(static, valid_fraction: float, gemm_bytes: float,
                       coeffs: CostCoeffs) -> float:
    """Host-side completion of `predict_plan_static`: add the executed-work
    terms from the step's returned fraction and bytes (scalars, or numpy
    arrays of many executions with `static` stacked to match). By
    construction equal to `predict_plan_time_s` on the same plan (tests pin
    the identity)."""
    const_s, total_tiles, tile, block_n = static
    flops = gemm_flops(valid_fraction * total_tiles, tile, block_n)
    return (const_s + gemm_bytes / coeffs.bytes_per_s
            + flops / coeffs.flops_per_s)


# ---------------------------------------------------------------------------
# the autotuner
# ---------------------------------------------------------------------------

class TunedParams(NamedTuple):
    """One weight's tuned blocking parameters + provenance. Hashable (a
    NamedTuple of primitives) so it rides `FrozenWeight`'s static aux
    through pytree flattening, and JSON-trivial so `PlanStore` persists it
    in the manifest (legacy manifests without it load as tuned=None)."""
    block_n: int
    levels: int
    bucket: int              # worklist bucket floor (`bucket(minimum=)`)
    predicted_us: float      # predicted per-call time at the tuned params
    default_predicted_us: float  # same model at the hardcoded defaults
    profile_key: str         # coefficients used ("interpret/cpu", ...)

    def as_manifest(self) -> dict:
        return dict(self._asdict())

    @classmethod
    def from_manifest(cls, d: Optional[dict]) -> Optional["TunedParams"]:
        if d is None:
            return None
        return cls(block_n=int(d["block_n"]), levels=int(d["levels"]),
                   bucket=int(d["bucket"]),
                   predicted_us=float(d["predicted_us"]),
                   default_predicted_us=float(d["default_predicted_us"]),
                   profile_key=str(d["profile_key"]))


BLOCK_N_CHOICES = (1, 2, 4)
LEVELS_CHOICES = (0, 1, 2)
BUCKET_CHOICES = (16, 64, 256)


def tune(
    norm_b: np.ndarray,
    tau: float,
    *,
    tile: int,
    dtype: str = "float32",
    coeffs: CostCoeffs,
    profile_key_used: str = "<nominal>",
    gm: int = DEFAULT_TUNE_GM,
    gm_hist: Optional[Mapping[int, float]] = None,
    norm_a: Optional[np.ndarray] = None,
    mode: str = "frozen",
    defaults: tuple = (1, 0, 16),
    block_n_choices: Sequence[int] = BLOCK_N_CHOICES,
    levels_choices: Sequence[int] = LEVELS_CHOICES,
    bucket_choices: Sequence[int] = BUCKET_CHOICES,
) -> TunedParams:
    """Argmin of predicted call time over block_n × levels × bucket floor.

    norm_b: the weight-side FINE normmap of the view the kernel multiplies
    (quantized view for low dtypes). tau: the GATE threshold (already
    widened for low dtypes). norm_a: a representative activation normmap;
    None prices with the all-ones activation (gate reduces to nb ≥ τ —
    deterministic, weight-structure-driven). gm_hist: an observed serving
    row-grid histogram {gm: weight} (`Engine.gm_histogram`) — candidates
    are then scored by the WEIGHTED SUM of predicted times over the grids
    a deployment actually runs instead of the single synthetic `gm`
    (an explicit `norm_a` carries its own grid and takes precedence). The
    defaults triple is always in the search space, so `predicted_us ≤
    default_predicted_us` by construction; ties keep the earliest
    candidate, and candidates are enumerated defaults-first then
    ascending, making the tuner a pure function of (norms, τ, grid
    weights, coefficients).
    """
    nb = np.asarray(norm_b, np.float64)
    gk = nb.shape[0]
    if norm_a is not None:
        na = np.asarray(norm_a, np.float64)
        grids = [(na, 1.0)]
    elif gm_hist:
        grids = [(np.ones((int(g), gk), np.float64), float(w))
                 for g, w in sorted(gm_hist.items()) if w > 0 and g > 0]
        if not grids:
            raise ValueError(f"gm_hist has no usable entries: {gm_hist!r}")
    else:
        grids = [(np.ones((gm, gk), np.float64), 1.0)]

    def predicted(bn: int, lv: int, bk_min: int) -> float:
        t = 0.0
        for na_g, w in grids:
            c = predict_counts(na_g, nb, float(tau), tile=tile, block_n=bn,
                               dtype=dtype, levels=lv, bucket_min=bk_min,
                               mode=mode)
            t += w * predict_time_s(c, coeffs)
        return t

    d_bn, d_lv, d_bk = defaults
    cands = [(int(d_bn), int(d_lv), int(d_bk))]
    for bn in block_n_choices:
        for lv in levels_choices:
            for bk_min in bucket_choices:
                c = (int(bn), int(lv), int(bk_min))
                if c not in cands:
                    cands.append(c)
    best, best_t, default_t = None, None, None
    for c in cands:
        t = predicted(*c)
        if default_t is None:
            default_t = t  # defaults are candidate 0
        if best_t is None or t < best_t:
            best, best_t = c, t
    return TunedParams(block_n=best[0], levels=best[1], bucket=best[2],
                       predicted_us=best_t * 1e6,
                       default_predicted_us=default_t * 1e6,
                       profile_key=profile_key_used)


def tune_weight(
    w,
    tau: float,
    *,
    tile: int,
    dtype: str = "float32",
    backend: str = "auto",
    profile: Optional[CostProfile] = None,
    gm: int = DEFAULT_TUNE_GM,
    gm_hist: Optional[Mapping[int, float]] = None,
    norm_a: Optional[np.ndarray] = None,
    mode: str = "frozen",
    defaults: tuple = (1, 0, 16),
    use_mxu: bool = False,
) -> TunedParams:
    """`tune` for a concrete weight matrix: computes the weight-side
    normmap of the QUANTIZED view (what a low-precision kernel multiplies)
    through the backend's get-norm (the fused int8 getnorm+absmax kernel
    when registered), widens τ by the analytic quantization bound, and
    prices with the profile's coefficients for the resolved backend.
    `gm_hist` (e.g. `Engine.gm_histogram`) prices over the observed
    serving row grids instead of the synthetic `gm`."""
    from repro.core.plan import pad_to_tile  # circular-safe at call time
    from repro.kernels import ops as kops

    bk = kops.get_backend(backend)
    profile = profile or CostProfile()
    coeffs = profile.coeffs(bk.name)
    dtype = kquant.canonical_dtype(dtype)
    import jax.numpy as jnp

    wp = pad_to_tile(jnp.asarray(w), tile)
    if dtype == "int8":
        nb, _ = kops.int8_norms_and_scales(wp, tile, backend=bk.name,
                                           use_mxu=use_mxu)
    elif dtype != "float32":
        nb = bk.norms(kquant.quantized_view(wp, dtype, tile), tile,
                      use_mxu=use_mxu)
    else:
        nb = bk.norms(wp, tile, use_mxu=use_mxu)
    tau_gate = float(np.asarray(kquant.widen_tau(float(tau), dtype, tile)))
    return tune(np.asarray(nb), tau_gate, tile=tile, dtype=dtype,
                coeffs=coeffs, profile_key_used=profile.key_used(bk.name),
                gm=gm, gm_hist=gm_hist, norm_a=norm_a, mode=mode,
                defaults=defaults)


# ---------------------------------------------------------------------------
# calibration: fit coefficients from measured kernel wall-clock
# ---------------------------------------------------------------------------

def _timeit_s(fn, *args, warmup: int = 1, repeat: int = 3) -> float:
    """Median wall-clock seconds per call (block_until_ready)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _nnls_refit(feats: np.ndarray, times: np.ndarray) -> np.ndarray:
    """Least squares with non-negativity enforced by zero-and-refit: solve,
    clamp negative coefficients to zero, refit the surviving columns (one
    pass — the 4-column design cannot oscillate)."""
    x, *_ = np.linalg.lstsq(feats, times, rcond=None)
    keep = x > 0
    if keep.all():
        return x
    out = np.zeros_like(x)
    if keep.any():
        sub, *_ = np.linalg.lstsq(feats[:, keep], times, rcond=None)
        out[keep] = np.maximum(sub, 0.0)
    return out


def calibrate(backend: str = "interpret", *, tile: int = 32,
              sizes: Sequence[int] = (128, 256, 384),
              taus: Sequence[float] = (0.0, 0.02, 0.2),
              seed: int = 0, repeat: int = 3) -> CostCoeffs:
    """Fit machine coefficients from measured kernel wall-clock.

    Samples get-norm runs (pure bandwidth) and work-list executes across τ
    (step count, bytes and flops all varying) on exponential-decay
    matrices, then solves the additive model of `predict_time_s` for
    [base, step_overhead, 1/bandwidth, 1/flops] by non-negative least
    squares. The host gate rate is measured directly on the flat-gate
    product. Wall-clock in, coefficients out — run once per machine and
    persist with `CostProfile.save`.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import plan as cplan  # circular-safe at call time
    from repro.core.spamm import exponential_decay
    from repro.kernels import ops as kops

    bk = kops.get_backend(backend)
    rows_f, times = [], []
    for n in sizes:
        x = jnp.asarray(exponential_decay(n, lam=0.7, seed=seed))
        t = _timeit_s(jax.jit(lambda v, _b=bk: _b.norms(v, tile)), x,
                      repeat=repeat)
        rows_f.append([1.0, 0.0, float(n * n * 4), 0.0])
        times.append(t)
    n = sizes[-1]
    a = jnp.asarray(exponential_decay(n, lam=0.7, seed=seed))
    b = jnp.asarray(exponential_decay(n, lam=0.7, seed=seed + 1))
    for tau in taus:
        for bn in (1, 2):
            p = cplan.plan(a, b, tau, tile=tile, block_n=bn,
                           backend=bk.name)
            t = _timeit_s(lambda p=p: cplan.execute(p, a, b), repeat=repeat)
            v = float(p.valid_tiles)
            pairs = float(np.sum(np.asarray(p.nvalid) > 0))
            steps = (float(p.work.step_i.shape[0])
                     if p.work is not None and p.work.step_i is not None
                     else v)
            rows_f.append([1.0, steps,
                           float(gemm_bytes(v, pairs, tile, bn, "float32")),
                           float(gemm_flops(v, tile, bn))])
            times.append(t)
    feats = np.asarray(rows_f, np.float64)
    x = _nnls_refit(feats, np.asarray(times, np.float64))
    base, step, inv_bw, inv_fl = x
    nominal = DEFAULT_COEFFS.get(bk.name, DEFAULT_COEFFS["jnp"])
    # gate rate: host flat-gate products per second, measured directly
    gm = gk = gn = max(sizes) // tile
    na = np.abs(np.random.default_rng(seed).normal(size=(gm, gk)))
    nb = np.abs(np.random.default_rng(seed + 1).normal(size=(gk, gn)))
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        (na[:, None, :] * nb.T[None] >= 0.5).sum()
    gate_rate = reps * gm * gk * gn / max(time.perf_counter() - t0, 1e-9)
    return CostCoeffs(
        bytes_per_s=(1.0 / inv_bw) if inv_bw > 0 else nominal.bytes_per_s,
        flops_per_s=(1.0 / inv_fl) if inv_fl > 0 else nominal.flops_per_s,
        step_overhead_s=float(step) if step > 0 else nominal.step_overhead_s,
        base_overhead_s=float(base) if base > 0 else nominal.base_overhead_s,
        gate_ops_per_s=float(gate_rate),
        calibrated=True,
    )
