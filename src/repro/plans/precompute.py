"""Offline plan precomputation: walk a model's gated GEMM weights and
freeze/store their weight-side plans.

The model zoo gates exactly the GEMMs that go through
`core.module.maybe_spamm_matmul`: the attention projections (wq/wk/wv/wo,
and latent attention's wkv_a/wkv_b, under a layer's "mix" subtree), the MLP
matmuls (w1/w3/w2 under "mlp") and the MoE shared experts' (under
"shared"). The held experts' stacked weights (w1/w3/w2 directly under
"moe") freeze per expert into `FrozenExperts`, which the expert layer's
work-list kernel reads (`core.module.spamm_experts_frozen`).

`freeze_tree` mirrors the params structure at those leaves: a stacked
(L, K, N) leaf becomes a list of per-layer `FrozenWeight`s (what
`stack_plans` later turns into scan inputs), a 2-D leaf a single one, and
an expert leaf (L, E, K, N) a list of per-layer `FrozenExperts`.
`populate` is the CLI-facing store writer (`repro.launch.precompute_plans`).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import numpy as np

from repro.plans.frozen import FrozenWeight, freeze_experts
from repro.plans.store import PlanStore, fingerprint

# leaf name × parent subtree that identifies a gated GEMM weight
GATED_NAMES = ("wq", "wk", "wv", "wo", "wkv_a", "wkv_b", "w1", "w2", "w3")
GATED_PARENTS = ("mix", "mlp", "shared")
EXPERT_NAMES = ("w1", "w2", "w3")        # under "moe": (..., E, K, N)


def iter_gated_weights(params, _prefix=()):
    """Yield (path_tuple, leaf) for every gated GEMM weight in a params
    pytree: leaves named wq/wk/wv/wo/wkv_a/wkv_b/w1/w2/w3 directly under a
    "mix", "mlp" or "shared" subtree. Stacked leaves (leading layer/group
    dim) are yielded whole; callers slice axis 0 per layer."""
    if not isinstance(params, dict):
        return
    for name, sub in params.items():
        path = _prefix + (name,)
        if isinstance(sub, dict):
            yield from iter_gated_weights(sub, path)
        elif (len(path) >= 2 and path[-2] in GATED_PARENTS
              and name in GATED_NAMES and getattr(sub, "ndim", 0) >= 2):
            yield path, sub


def iter_expert_weights(params, _prefix=()):
    """Yield (path_tuple, leaf) for every MoE layer's stacked expert weight
    (w1/w3/w2 directly under "moe", shaped (..., E, K, N))."""
    if not isinstance(params, dict):
        return
    for name, sub in params.items():
        path = _prefix + (name,)
        if isinstance(sub, dict):
            yield from iter_expert_weights(sub, path)
        elif (len(path) >= 2 and path[-2] == "moe" and name in EXPERT_NAMES
              and getattr(sub, "ndim", 0) >= 3):
            yield path, sub


def tune_for(w, scfg, *, profile=None, use_mxu: bool = False):
    """Autotune one weight's blocking parameters against the roofline cost
    model: argmin of predicted frozen-call time over block_n × levels ×
    bucket floor, with the config's own (block_n, levels, 16) always in the
    search space (the tuned pick is never predicted slower). `profile` is a
    loaded `core.cost.CostProfile`; None loads `scfg.tune_profile` (or the
    nominal per-backend coefficients)."""
    from repro.core import cost  # deferred: precompute imports stay light

    if profile is None:
        profile = cost.CostProfile.load_or_default(
            getattr(scfg, "tune_profile", None))
    return cost.tune_weight(
        w, scfg.tau, tile=scfg.tile,
        dtype=getattr(scfg, "dtype", "float32"), backend=scfg.backend,
        profile=profile,
        defaults=(scfg.block_n, getattr(scfg, "levels", 0), 16),
        use_mxu=use_mxu)


def _freeze_one(w, scfg, *, cache=None, store: Optional[PlanStore] = None,
                use_mxu: bool = False, tuned=None,
                profile=None) -> FrozenWeight:
    """One weight → FrozenWeight, through the cache/store tiers when given.

    With `scfg.autotune` the artifact is frozen at the TUNED block_n/levels
    (which address it in the store) and carries the `TunedParams` record;
    pass `tuned` explicitly to reuse one tuning across stacked layer slices
    (stacked plans must share static metadata — see `stack_plans`)."""
    if tuned is None and getattr(scfg, "autotune", False):
        tuned = tune_for(w, scfg, profile=profile, use_mxu=use_mxu)
    block_n = tuned.block_n if tuned is not None else scfg.block_n
    levels = (tuned.levels if tuned is not None
              else getattr(scfg, "levels", 0))
    kw = dict(tau=scfg.tau, tile=scfg.tile, block_n=block_n, levels=levels,
              backend=scfg.backend)
    dtype = getattr(scfg, "dtype", "float32")
    if cache is not None:
        return cache.frozen_weight(w, use_mxu=use_mxu, store=store,
                                   dtype=dtype, tuned=tuned, **kw)
    h = fingerprint(w)
    if store is not None:
        # may raise PlanStoreError on stale artifacts
        fw = store.get(h, use_mxu=use_mxu, dtype=dtype, **kw)
        if fw is not None:
            return fw
    fw = FrozenWeight.build(w, use_mxu=use_mxu, weight_hash=h,
                            compute_dtype=dtype, tuned=tuned, **kw)
    if store is not None:
        store.put(fw)
    return fw


def freeze_tree(params, scfg, *, cache=None, store: Optional[PlanStore] = None,
                use_mxu: bool = False, span=None):
    """Freeze every gated weight of a params pytree.

    Returns (tree, count): `tree` mirrors the params dict structure at the
    gated leaves, each leaf a `FrozenWeight` (2-D weight) or a list of
    per-layer `FrozenWeight`s (stacked weight); `count` is the number of
    distinct weight matrices frozen. `cache` (a `WeightPlanCache`) is the
    in-memory tier; `store` the persistent one — with a warm store this
    whole walk is load-only, no get-norm pass.

    With `scfg.autotune`, each 2-D weight is tuned individually; a stacked
    leaf is tuned ONCE (from its first slice) and every layer slice is
    frozen at that shared config — stacked per-layer plans must agree on
    block_n/levels/bucket to ride one lax.scan (`stack_plans`).

    `span(name, **args)` (a context-manager factory, e.g. an
    `Observability.span`) times the expert weights' freezing as
    "freeze_experts"."""
    autotune = getattr(scfg, "autotune", False)
    profile = None
    if autotune:
        from repro.core import cost  # deferred: precompute imports stay light

        profile = cost.CostProfile.load_or_default(
            getattr(scfg, "tune_profile", None))
    count = 0
    tree: dict = {}
    for path, leaf in iter_gated_weights(params):
        if leaf.ndim == 2:
            fz = _freeze_one(leaf, scfg, cache=cache, store=store,
                             use_mxu=use_mxu, profile=profile)
            count += 1
        else:
            # stacked (L, K, N): freeze per layer slice (flattening extra
            # leading dims first keeps hybrid group stacks uniform)
            flat = np.asarray(leaf).reshape(-1, *leaf.shape[-2:])
            tuned = (tune_for(flat[0], scfg, profile=profile,
                              use_mxu=use_mxu) if autotune else None)
            fz = [
                _freeze_one(flat[l], scfg, cache=cache, store=store,
                            use_mxu=use_mxu, tuned=tuned)
                for l in range(flat.shape[0])
            ]
            count += flat.shape[0]
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = fz
    for path, leaf in iter_expert_weights(params):
        # (L, E, K, N) or (E, K, N): one FrozenWeight per expert, grouped
        # per layer into FrozenExperts (expert plans are never autotuned:
        # every expert of a stack shares one kb and one step count)
        with (span("freeze_experts", site="/".join(path)) if span
              else contextlib.nullcontext()):
            flat = np.asarray(leaf).reshape(-1, *leaf.shape[-3:])
            ecfg = dataclasses.replace(scfg, block_n=1, levels=0,
                                       dtype="float32", autotune=False)
            fws = [[_freeze_one(flat[l, e], ecfg, cache=cache, store=store,
                                use_mxu=use_mxu)
                    for e in range(flat.shape[1])]
                   for l in range(flat.shape[0])]
            count += flat.shape[0] * flat.shape[1]
            fz = freeze_experts(fws)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = fz if leaf.ndim > 3 else fz[0]
    return tree, count


def populate(store: PlanStore, params, scfg, *, cache=None,
             use_mxu: bool = False) -> int:
    """Populate `store` with frozen plans for every gated GEMM weight of
    `params` under SpAMM config `scfg`. Returns the number of weights
    processed (store hits + fresh builds)."""
    _, count = freeze_tree(params, scfg, cache=cache, store=store,
                           use_mxu=use_mxu)
    return count
