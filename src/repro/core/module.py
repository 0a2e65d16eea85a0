"""SpAMM as a drop-in layer for the model zoo (paper §4.3: ergo + VGG13 show
SpAMM embedded in larger applications; here it replaces x @ W GEMMs).

`spamm_linear(x, w, ...)` flattens leading dims, zero-pads to tile multiples,
builds a `SpammPlan` (weight side optionally served from a `WeightPlanCache`)
and executes it. Differentiable via custom_vjp:

  * bwd="dense" (default): exact dense gradients — the paper accelerates
    inference only, so training keeps unbiased grads while the forward enjoys
    tile skipping.
  * bwd="spamm": gradients gated with plans DERIVED from the forward plan's
    normmaps (dx gates g @ Wᵀ with norms(g)·norms(W)ᵀ, dw gates xᵀ @ g with
    norms(x)ᵀ·norms(g)) — a beyond-paper mode trading gradient exactness for
    symmetric FLOP savings. The weight/activation normmaps are computed once
    in the forward and reused, not recomputed per gradient.

The model zoo threads a single `SpammContext` (config + shared
WeightPlanCache) instead of raw (tau, tile, backend, block_n) tuples — see
`maybe_spamm_matmul`.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import cost as _cost
from repro.core import plan as _plan
from repro.core.plan import WeightPlanCache, pad_to_tile


class GemmTap(NamedTuple):
    """One gated GEMM's telemetry as traced values, held in the context's
    trace buffer while a step is traced.

    `frac` and `nbytes` are traced f32 arrays of one shape: one element per
    execution (a scalar for a plain GEMM, one entry per expert under the
    MoE block's vmap). `site` ("wq", "w1", ...) and `cost` (the static part
    of the cost prediction, `cost.predict_plan_static`, or None) are host
    values captured at trace time."""
    site: str
    frac: Any
    nbytes: Any
    cost: Optional[tuple] = None


@jax.tree_util.register_pytree_node_class
class StepStats:
    """The gating stats one compiled step returns beside its outputs.

    `values` is f32 `(..., N, 2)`: per tap row, the executed GEMM's
    [valid_fraction, bytes_moved]; leading dims are further executions of
    the same rows (the shards of a pod-sharded step). `layout` is static
    (the pytree's aux data, so it leaves `jit` with the output and every
    compiled shape carries its own): per row, `(layer, site, cost)`."""

    __slots__ = ("values", "layout")

    def __init__(self, values, layout: tuple):
        self.values = values
        self.layout = layout

    def tree_flatten(self):
        return (self.values,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, leaves):
        return cls(leaves[0], layout)


def _pack(taps) -> tuple:
    """`(values (n, 2) f32, rows)` from drained `GemmTap`s: every element
    of a tap is one row, and `rows[r]` is its `(site, cost)`."""
    if not taps:
        return jnp.zeros((0, 2), jnp.float32), ()
    cols = [jnp.stack([jnp.ravel(t.frac).astype(jnp.float32),
                       jnp.ravel(t.nbytes).astype(jnp.float32)], -1)
            for t in taps]
    rows = tuple(r for t in taps
                 for r in [(t.site, t.cost)] * math.prod(jnp.shape(t.frac)))
    return jnp.concatenate(cols), rows


def collect_taps(ctx: Optional["SpammContext"], fn, *args, **kwargs):
    """`(fn(*args, **kwargs), values (n, 2) f32, rows)`: fn run with its
    gated GEMMs tapped into a buffer of its own, packed — one row per
    execution, `rows[r]` its static `(site, cost)`. The values are traced
    in fn's trace, so a scan, vmap or shard_map body returns them as an
    output. Without a context: `(fn(...), None, ())`."""
    if ctx is None:
        return fn(*args, **kwargs), None, ()
    with ctx.collecting() as taps:
        out = fn(*args, **kwargs)
    return (out,) + _pack(taps)


def retap(ctx: Optional["SpammContext"], values, rows) -> None:
    """Record packed taps (`values (..., n, 2)` and `rows` from
    `collect_taps` in an inner trace, which returned them as outputs) into
    `ctx`'s open trace buffer."""
    if ctx is None:
        return
    for j, (site, cost) in enumerate(rows):
        ctx.tap(values[..., j, 0], values[..., j, 1], site=site,
                cost_static=cost)


class SpammContext:
    """Static SpAMM execution context for the model zoo: the `SpammConfig`
    plus a `WeightPlanCache` shared across every gated GEMM of a model.

    Hashed by identity (usable as a jit static / custom_vjp nondiff arg);
    create one per model/engine, not per call.

    Gating telemetry rides the traced computation as values: while a trace
    buffer is open (`collecting()`), every gated GEMM appends a `GemmTap`
    of its traced valid fraction and bytes moved. The layer stacks open one
    buffer per layer inside the scan body, return the packed taps as the
    scan's ys, and the compiled step hands them back as a `StepStats`
    output; no host callback runs inside a step. The same dataflow works
    under `grad` and remat (the train step's metrics) and out of vmap and
    shard_map bodies (the MoE block returns its expert taps as outputs).

    Cost telemetry (optional): with `enable_cost_taps(coeffs)`, frozen-path
    GEMMs also capture the static part of their time prediction at trace
    time (`cost.predict_plan_static`, host floats kept in the tap's static
    layout); the host finishes it from the returned fraction and bytes
    (`cost.finish_plan_time_s`), so arming adds no op to the compiled step.
    """

    __slots__ = ("cfg", "cache", "_trace_buffer", "cost_coeffs")

    def __init__(self, cfg: Any, cache: Optional[WeightPlanCache] = None):
        self.cfg = cfg
        self.cache = cache if cache is not None else WeightPlanCache()
        self._trace_buffer: Optional[list] = None
        self.cost_coeffs = None

    def __repr__(self):
        return f"SpammContext({self.cfg!r}, cache={len(self.cache)} entries)"

    @property
    def enable(self) -> bool:
        return bool(getattr(self.cfg, "enable", False))

    # -- gating telemetry ---------------------------------------------------
    def enable_cost_taps(self, coeffs):
        """Arm the cost-prediction channel: `coeffs` is a `cost.CostCoeffs`
        (host floats, resolved once per engine from the tune profile). Set
        it BEFORE the first trace of the instrumented step: the static
        terms are captured while tracing."""
        self.cost_coeffs = coeffs

    @property
    def is_collecting(self) -> bool:
        """True while a trace buffer is open."""
        return self._trace_buffer is not None

    @contextlib.contextmanager
    def collecting(self):
        """Open a fresh trace buffer for the enclosed trace and yield it;
        the enclosing buffer (if any) is restored on exit, so a buffer
        never holds tracers of another trace level (a scan body, a vmap or
        a shard_map body each collect their own and return them)."""
        prev, self._trace_buffer = self._trace_buffer, []
        try:
            yield self._trace_buffer
        finally:
            self._trace_buffer = prev

    def tap(self, valid_fraction, nbytes, site: str = "",
            cost_static: Optional[tuple] = None):
        """Record one gated GEMM's traced valid fraction and bytes moved
        (same shape: one element per execution) into the open trace
        buffer; a no-op when none is open."""
        if self._trace_buffer is None:
            return
        self._trace_buffer.append(GemmTap(site or "", valid_fraction, nbytes,
                                          cost_static))


def as_context(spamm_cfg) -> Optional[SpammContext]:
    """Normalize what the model zoo threads: None / SpammConfig /
    SpammContext all become an Optional[SpammContext]."""
    if spamm_cfg is None or isinstance(spamm_cfg, SpammContext):
        return spamm_cfg
    return SpammContext(spamm_cfg)


def _flatten_pad(x, tile):
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for s in lead:
        m *= s
    x2 = x.reshape(m, k)
    return pad_to_tile(x2, tile), (lead, m, k)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def _spamm_linear_stats(
    x: jax.Array,
    w: jax.Array,
    tau: jax.Array,
    tile: int = 64,
    backend: str = "auto",
    bwd: str = "dense",
    block_n: int = 1,
    ctx: Optional[SpammContext] = None,
    levels: int = 0,
    compute_dtype: str = "float32",
):
    """(y, (valid_fraction, bytes_moved)) — the gated GEMM plus its gating
    stats as REAL OUTPUTS. The stats must flow out of the custom_vjp rather
    than be tapped inside it: the fwd rule is traced in its own subsidiary
    trace under autodiff, so a tracer appended to a buffer there would leak.
    Callers tap the returned values."""
    y, p = _fwd_impl(x, w, tau, tile, backend, block_n, ctx, levels,
                     compute_dtype)
    return y, (p.valid_fraction, p.bytes_moved())


def spamm_linear(
    x: jax.Array,
    w: jax.Array,
    tau: jax.Array,
    tile: int = 64,
    backend: str = "auto",
    bwd: str = "dense",
    block_n: int = 1,
    ctx: Optional[SpammContext] = None,
    levels: int = 0,
    compute_dtype: str = "float32",
) -> jax.Array:
    """y[..., n] = SpAMM(x[..., k] @ w[k, n], tau). Output dtype follows x.

    `ctx` (optional, static) supplies the WeightPlanCache so eager callers
    (serving) pay the weight-side gating once per weight. `levels` > 0 plans
    hierarchically over the norm pyramid (mask unchanged, planning cheaper;
    the weight-side pyramid is what the cache then holds). `compute_dtype`
    selects the forward GEMM operand precision (float32 | bfloat16 | int8 —
    f32 accumulate, conservative widened-τ gate); gradients always run f32.
    """
    return _spamm_linear_stats(x, w, tau, tile, backend, bwd, block_n, ctx,
                               levels, compute_dtype)[0]


def _fwd_impl(x, w, tau, tile, backend, block_n, ctx, levels=0,
              compute_dtype="float32"):
    """Plan + execute one gated GEMM; returns (y, plan)."""
    xp, (lead, m, k) = _flatten_pad(x, tile)
    n = w.shape[-1]
    if ctx is not None:
        p, wp = ctx.cache.plan_for(
            xp, w, tau, tile=tile, block_n=block_n, backend=backend,
            levels=levels, compute_dtype=compute_dtype,
        )
    else:
        # N pads to tile·block_n (not just tile) so odd-N weights survive
        # super-column gating; the cache path does the same in weight_side
        wp = pad_to_tile(w, tile, tile * block_n)
        p = _plan.plan(xp, wp, tau, tile=tile, block_n=block_n,
                       backend=backend, levels=levels,
                       compute_dtype=compute_dtype)
    c = _plan.execute(p, xp, wp)
    y = c[:m, :n].reshape(*lead, n).astype(x.dtype)
    return y, p


def _spamm_linear_fwd(x, w, tau, tile, backend, bwd, block_n, ctx, levels,
                      compute_dtype):
    y, p = _fwd_impl(x, w, tau, tile, backend, block_n, ctx, levels,
                     compute_dtype)
    # residuals carry the forward normmaps so bwd="spamm" replans without
    # re-running get-norm on x or w
    return ((y, (p.valid_fraction, p.bytes_moved())),
            (x, w, tau, p.norm_a, p.norm_b))


def _spamm_linear_bwd(tile, backend, bwd, block_n, ctx, levels, compute_dtype,
                      res, g):
    # gradients deliberately ignore compute_dtype: bwd="dense" is exact f32
    # by contract, and bwd="spamm" regates from the forward normmaps (already
    # quantization-aware via the widened forward τ) but multiplies in f32 —
    # low-precision grads would bias training for no serving win
    del compute_dtype
    x, w, tau, norm_x, norm_w = res
    g, _ = g  # cotangents of the gating-stat outputs are discarded
    lead = x.shape[:-1]
    k, n = w.shape
    m = 1
    for s in lead:
        m *= s
    g2 = g.reshape(m, n)
    x2 = x.reshape(m, k)
    if bwd == "dense":
        dx = (g2 @ w.T).reshape(x.shape).astype(x.dtype)
        dw = (x2.T @ g2).astype(w.dtype)
    elif bwd == "spamm":
        # g/w pad N to tile·block_n to match the forward normmaps' column
        # grid (norm_w came from the block_n-padded weight)
        gp = pad_to_tile(g2, tile, tile * block_n)
        xp = pad_to_tile(x2, tile)
        wp = pad_to_tile(w, tile, tile * block_n)
        # dx = (g @ Wᵀ) gated by norms(g)·norms(W)ᵀ — the forward bitmap
        # with its (k, j) axes transposed, built from the cached weight norms
        p_dx = _plan.plan(gp, None, tau, norm_b=norm_w.T, tile=tile,
                          backend=backend)
        norm_g = p_dx.norm_a
        dxp = _plan.execute(p_dx, gp, wp.T)
        # dw = (xᵀ @ g) gated by norms(x)ᵀ·norms(g)
        p_dw = _plan.plan(None, None, tau, norm_a=norm_x.T, norm_b=norm_g,
                          tile=tile, backend=backend)
        dwp = _plan.execute(p_dw, xp.T, gp)
        dx = dxp[:m, :k].reshape(x.shape).astype(x.dtype)
        dw = dwp[:k, :n].astype(w.dtype)
    else:
        raise ValueError(f"bwd={bwd!r}")
    dtau = jnp.zeros_like(jnp.asarray(tau, jnp.float32))
    return dx, dw, dtau


_spamm_linear_stats.defvjp(_spamm_linear_fwd, _spamm_linear_bwd)


def spamm_bmm_linear(x: jax.Array, w: jax.Array, spamm_ctx) -> jax.Array:
    """Batched gated GEMM for per-slice weights (B, K, N) — the MoE grouped
    FFN shape — via `core.plan.spamm_bmm` with a shared τ. Forward-gated
    only (used on inference/eval paths; training MoE keeps dense grads)."""
    cfg = spamm_ctx.cfg
    c, info = _plan.spamm_bmm(
        x, w, jnp.asarray(cfg.tau, jnp.float32),
        tile=cfg.tile, block_n=cfg.block_n, backend=cfg.backend,
        cache=spamm_ctx.cache, levels=getattr(cfg, "levels", 0),
    )
    # spamm_bmm reports no byte count: NaN marks the tap's bytes as unknown
    spamm_ctx.tap(info.valid_fraction, jnp.float32(jnp.nan), site="moe_bmm")
    return c.astype(x.dtype)


def spamm_linear_frozen(x: jax.Array, w: jax.Array, fp,
                        ctx: Optional[SpammContext] = None,
                        site: Optional[str] = None) -> jax.Array:
    """Gated GEMM with a frozen weight side (forward-only serving path).

    `fp` is a `repro.plans.frozen.FrozenPlan` specialized to x's flattened
    row grid, passed INTO the enclosing jit as an argument: the traced graph
    computes only the activation-side gate and runs the frozen `SpammWork`
    step tables — no weight get-norm, no dense-bitmap sort. Bit-identical to
    `spamm_linear` with the same config (the frozen tables are a superset
    re-gated by the exact flat τ-test). Inference path: no custom_vjp.

    `site` labels the tap ("wq", "w1", ...). When the context has cost taps
    armed, the static part of the predicted call time is computed HERE at
    trace time and kept in the tap's static layout; the host finishes it
    from the returned fraction and bytes, so arming adds no graph op."""
    tile = fp.tile
    xp, (lead, m, k) = _flatten_pad(x, tile)
    n = w.shape[-1]
    p = _plan.plan(xp, frozen_weight=fp)
    if ctx is not None and ctx.is_collecting:
        cost = (_cost.predict_plan_static(p, ctx.cost_coeffs)
                if ctx.cost_coeffs is not None else None)
        ctx.tap(p.valid_fraction, p.bytes_moved(), site=site,
                cost_static=cost)
    wp = pad_to_tile(w, tile, tile * fp.block_n)
    c = _plan.execute(p, xp, wp)
    return c[:m, :n].reshape(*lead, n).astype(x.dtype)


def maybe_spamm_matmul(x: jax.Array, w: jax.Array, spamm_cfg: Any,
                       frozen=None, require_frozen: bool = False,
                       site: str = "") -> jax.Array:
    """The hook the model zoo calls for every eligible GEMM: dense when
    spamm_cfg is disabled, plan-routed spamm_linear when enabled.
    `spamm_cfg` may be a SpammConfig or a SpammContext (cfg + plan cache).

    `frozen` (a FrozenPlan jit input) routes the GEMM through the frozen
    work-list path instead of tracing the gate from scratch.
    `require_frozen=True` (the decode path) falls back to DENSE when no
    frozen plan is available for this site — decode-step gating is only
    worth its trace when the weight side comes precomputed.
    `site` is a static per-GEMM label ("wq", "w2", ...) for the telemetry."""
    ctx = as_context(spamm_cfg)
    if ctx is None or not ctx.enable or (require_frozen and frozen is None):
        return x @ w
    if frozen is not None:
        return spamm_linear_frozen(x, w, frozen, ctx, site=site)
    cfg = ctx.cfg
    y, (frac, nbytes) = _spamm_linear_stats(
        x,
        w,
        jnp.asarray(cfg.tau, jnp.float32),
        cfg.tile,
        cfg.backend,
        cfg.bwd,
        cfg.block_n,
        ctx,
        getattr(cfg, "levels", 0),
        getattr(cfg, "dtype", "float32"),
    )
    ctx.tap(frac, nbytes, site=site)
    return y
