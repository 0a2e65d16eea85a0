"""Roofline cost model + autotuner — ISSUE 7 tentpole coverage.

Pins the contracts the tuner rests on: the analytic byte counts ARE
`SpammPlan.bytes_moved()` (one formula, `core.cost.gemm_bytes`) across
dtype × block_n × levels; tuning is deterministic under a fixed profile
and never predicted slower than the hardcoded defaults; `TunedParams`
round-trips through the `PlanStore` manifest while legacy artifacts
(no tuned record) still load; the fused int8 getnorm+absmax kernel is
bit-identical to the unfused quantize→dequantize→getnorm pipeline; and
the perf-trajectory gate (`benchmarks.perf_gate`) fails on an injected
slowdown and refuses cross-environment comparisons.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost
from repro.core import plan as pl
from repro.core.spamm import exponential_decay
from repro.kernels import ops as kops
from repro.kernels import quantize as kquant
from repro.plans.frozen import FrozenWeight
from repro.plans.store import PlanStore, fingerprint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # benchmarks.* imports when pytest cwd ≠ repo root
    sys.path.insert(0, REPO)

N, TILE, TAU, LAM = 128, 32, 0.05, 0.8


def _pair(n=N, lam=LAM):
    a = jnp.asarray(exponential_decay(n, lam=lam, seed=0))
    b = jnp.asarray(exponential_decay(n, lam=lam, seed=1))
    return a, b


def _flat(norm):
    return np.asarray(norm.levels[0] if hasattr(norm, "levels") else norm)


# ---------------------------------------------------------------------------
# counts: the model's bytes ARE the plan's bytes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("block_n", [1, 2])
@pytest.mark.parametrize("levels", [0, 1])
def test_predicted_bytes_equal_plan_bytes_moved(dtype, block_n, levels):
    a, b = _pair()
    p = pl.plan(a, b, TAU, tile=TILE, block_n=block_n, levels=levels,
                backend="interpret", compute_dtype=dtype)
    # the plan stores the WIDENED τ and the quantized-view normmaps — the
    # exact inputs the gate ran on, so the model must reproduce it exactly
    # at the k-tiles a step the plan chose
    counts = cost.predict_counts(
        _flat(p.norm_a), _flat(p.norm_b), float(p.tau), tile=TILE,
        block_n=block_n, dtype=dtype, levels=levels, mode="eager", kb=p.kb)
    assert counts.steps_real == int(p.valid_tiles)
    assert counts.steps_grid == p.work.step_i.shape[0]
    assert counts.gemm_bytes == pytest.approx(float(p.bytes_moved()), rel=0,
                                              abs=0.5)
    # and the formula itself is shared, not duplicated
    pairs = int(np.sum(np.asarray(p.nvalid) > 0))
    assert counts.pairs == pairs
    assert counts.gemm_bytes == cost.gemm_bytes(
        counts.steps_real, pairs, TILE, block_n, dtype, kb=p.kb,
        steps=int(p.steps))


def test_gemm_bytes_dtype_itemsize_aware():
    v, pairs = 10.0, 4.0
    b32 = cost.gemm_bytes(v, pairs, TILE, 1, "float32")
    b16 = cost.gemm_bytes(v, pairs, TILE, 1, "bfloat16")
    b8 = cost.gemm_bytes(v, pairs, TILE, 1, "int8")
    flush = pairs * TILE * TILE * 4.0  # f32 output flush, dtype-independent
    assert (b32 - flush) == 2 * (b16 - flush) == 4 * (b8 - flush)


def test_bucket_min_threads_through_plan():
    a, b = _pair()
    p16 = pl.plan(a, b, TAU, tile=TILE, backend="interpret")
    p256 = pl.plan(a, b, TAU, tile=TILE, backend="interpret",
                   bucket_min=256)
    assert p16.work.step_i.shape[0] == cost.bucket(int(p16.steps))
    assert p256.work.step_i.shape[0] == 256
    np.testing.assert_array_equal(np.asarray(pl.execute(p16, a, b)),
                                  np.asarray(pl.execute(p256, a, b)))


# ---------------------------------------------------------------------------
# tuner: deterministic, never predicted slower than the defaults
# ---------------------------------------------------------------------------

def _fixed_profile():
    prof = cost.CostProfile()
    prof.put("interpret", cost.CostCoeffs(2.0e9, 1.0e10, 4.0e-5, 3.0e-4,
                                          2.0e8, calibrated=True),
             kind="testkind")
    return prof


def test_tune_weight_deterministic_and_never_worse():
    _, b = _pair()
    prof = _fixed_profile()
    tps = [cost.tune_weight(b, TAU, tile=TILE, dtype="int8",
                            backend="interpret", profile=prof)
           for _ in range(2)]
    assert tps[0] == tps[1]
    tp = tps[0]
    assert tp.predicted_us <= tp.default_predicted_us
    assert tp.block_n in cost.BLOCK_N_CHOICES
    assert tp.levels in cost.LEVELS_CHOICES
    assert tp.bucket in cost.BUCKET_CHOICES
    assert tp.profile_key == "interpret/testkind"


def test_tune_defaults_always_in_search_space():
    # when the caller's defaults ARE the argmin, the tuner must return them
    # exactly (defaults are always a candidate, strict-< to replace) — so a
    # tuned pick can never be predicted slower than what it replaces
    _, b = _pair()
    prof = _fixed_profile()
    best = cost.tune_weight(b, TAU, tile=TILE, backend="interpret",
                            profile=prof)
    tp = cost.tune_weight(b, TAU, tile=TILE, backend="interpret",
                          profile=prof,
                          defaults=(best.block_n, best.levels, best.bucket))
    assert (tp.block_n, tp.levels, tp.bucket) == (
        best.block_n, best.levels, best.bucket)
    assert tp.predicted_us == tp.default_predicted_us == best.predicted_us


def test_profile_json_round_trip(tmp_path):
    prof = _fixed_profile()
    path = prof.save(str(tmp_path / "prof.json"))
    back = cost.CostProfile.load(path)
    assert back.coeffs("interpret") == prof.coeffs("interpret")
    assert back.coeffs("interpret").calibrated
    # schema guard: a future-schema file must refuse, not half-load
    with open(path) as f:
        doc = json.load(f)
    doc["schema"] = cost.COST_SCHEMA_VERSION + 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="schema"):
        cost.CostProfile.load(str(bad))
    # load_or_default: missing path → usable nominal profile
    nominal = cost.CostProfile.load_or_default(str(tmp_path / "nope.json"))
    assert nominal.coeffs("interpret") == cost.DEFAULT_COEFFS["interpret"]


# ---------------------------------------------------------------------------
# persistence: TunedParams through FrozenWeight aux + PlanStore manifest
# ---------------------------------------------------------------------------

def _tuned(block_n=2, levels=0, bucket=64):
    return cost.TunedParams(block_n=block_n, levels=levels, bucket=bucket,
                            predicted_us=12.5, default_predicted_us=20.0,
                            profile_key="interpret/testkind")


def test_planstore_round_trips_tuned_fields(tmp_path):
    _, b = _pair()
    tp = _tuned()
    fw = FrozenWeight.build(b, TAU, tile=TILE, block_n=tp.block_n,
                            levels=tp.levels, backend="interpret",
                            weight_hash=fingerprint(b), tuned=tp)
    assert fw.tuned == tp
    assert fw.bucket_floor == tp.bucket
    store = PlanStore(str(tmp_path / "store"))
    store.put(fw)
    back = PlanStore(str(tmp_path / "store")).get(  # fresh handle: disk only
        fingerprint(b), tau=TAU, tile=TILE, block_n=tp.block_n,
        levels=tp.levels, backend="interpret")
    assert back is not None
    assert back.tuned == tp
    assert back.bucket_floor == tp.bucket
    # the tuned bucket floors the step tables of every row-grid plan
    assert back.for_rows(2).num_steps >= tp.bucket


def test_planstore_legacy_artifacts_load_without_tuned(tmp_path):
    _, b = _pair()
    fw = FrozenWeight.build(b, TAU, tile=TILE, backend="interpret",
                            weight_hash=fingerprint(b))
    store = PlanStore(str(tmp_path / "store"))
    store.put(fw)
    # the manifest of an un-tuned artifact has NO tuned key (format
    # unchanged — old readers keep working on new stores)
    mans = [os.path.join(r, f) for r, _, fs in os.walk(str(tmp_path))
            for f in fs if f.endswith(".json")]
    assert mans
    for m in mans:
        with open(m) as f:
            assert "tuned" not in json.load(f)
    back = PlanStore(str(tmp_path / "store")).get(
        fingerprint(b), tau=TAU, tile=TILE, block_n=1, levels=0,
        backend="interpret")
    assert back is not None
    assert back.tuned is None
    assert back.bucket_floor == 16


def test_frozen_execute_matches_eager_at_tuned_params():
    a, b = _pair()
    tp = cost.tune_weight(b, TAU, tile=TILE, dtype="int8",
                          backend="interpret", profile=_fixed_profile())
    fw = FrozenWeight.build(b, TAU, tile=TILE, block_n=tp.block_n,
                            levels=tp.levels, backend="interpret",
                            compute_dtype="int8", tuned=tp)
    p_frozen = pl.plan(a, frozen_weight=fw, tile=TILE, backend="interpret")
    p_eager = pl.plan(a, b, TAU, tile=TILE, block_n=tp.block_n,
                      levels=tp.levels, backend="interpret",
                      compute_dtype="int8", bucket_min=tp.bucket)
    np.testing.assert_array_equal(np.asarray(pl.execute(p_frozen, a, b)),
                                  np.asarray(pl.execute(p_eager, a, b)))


# ---------------------------------------------------------------------------
# fused int8 getnorm+absmax kernel (satellite): bit-parity with unfused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_fused_int8_norms_match_unfused(backend):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))
    tile = 32
    norms, scales = kops.int8_norms_and_scales(x, tile, backend=backend)
    bk = kops.get_backend(backend)
    q, s_ref = kquant.quantize_tiles(x, tile)
    dq = kquant.dequantize_tiles(q, s_ref, tile)
    norms_ref = bk.norms(dq, tile)
    np.testing.assert_array_equal(np.asarray(norms), np.asarray(norms_ref))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(s_ref))
    assert norms.shape == (96 // tile, 64 // tile)


def test_fused_path_registered_only_where_it_exists():
    assert kops.BACKENDS["interpret"].norms_quant is not None
    assert kops.BACKENDS["pallas"].norms_quant is not None
    assert kops.BACKENDS["jnp"].norms_quant is None  # falls back, same bits


# ---------------------------------------------------------------------------
# perf-trajectory gate (benchmarks.perf_gate) + env-stamped reports
# ---------------------------------------------------------------------------

def test_write_bench_json_stamps_env(tmp_path):
    from benchmarks.report import BENCH_SCHEMA_VERSION, write_bench_json

    path = write_bench_json("stamptest", {"cells": [{"n": 1, "us": 2.0}]},
                            out_dir=str(tmp_path), backend="interpret")
    with open(path) as f:
        doc = json.load(f)
    assert doc["bench_schema_version"] == BENCH_SCHEMA_VERSION
    for env in (doc["env"], doc["data"]["cells"][0]["env"]):
        assert env["backend"] == "interpret"
        assert env["device_kind"]
        assert env["hostname"]


def test_perf_gate_fails_injected_slowdown_and_refuses_env_mismatch():
    from benchmarks import perf_gate

    ref = perf_gate._synthetic_doc()
    clean = perf_gate.compare_docs(ref, perf_gate._synthetic_doc(), "t")
    assert clean.ok and clean.checked > 0

    slow = perf_gate.compare_docs(
        ref,
        perf_gate._synthetic_doc(
            us=100.0 * (1 + perf_gate.WALL_CLOCK_REL_TOL) * 1.01), "t")
    assert not slow.ok
    assert any("wall-clock regressed" in p for p in slow.problems)

    moved = perf_gate.compare_docs(
        ref, perf_gate._synthetic_doc(device_kind="TPU v5e"), "t")
    assert moved.refusals and not moved.problems and not moved.ok

    # deterministic outputs gate BOTH directions — silent improvements
    # also demand a conscious reference update
    drift = perf_gate.compare_docs(
        ref, perf_gate._synthetic_doc(bytes_moved=0.9e6), "t")
    assert not drift.ok


def test_perf_gate_full_selftest():
    from benchmarks import perf_gate

    assert perf_gate.selftest() == 0


# ---------------------------------------------------------------------------
# freeze_tree autotune integration: stacked leaves share ONE tuning
# ---------------------------------------------------------------------------

def test_freeze_tree_autotune_attaches_shared_tuned(tmp_path):
    from repro.configs import SpammConfig
    from repro.plans.precompute import freeze_tree

    rng = np.random.default_rng(0)
    params = {"layers": {"mlp": {
        "w1": rng.standard_normal((2, 64, 64)).astype(np.float32),
        "w2": rng.standard_normal((64, 64)).astype(np.float32),
    }}}
    scfg = SpammConfig(enable=True, tau=0.02, tile=32, backend="interpret",
                       autotune=True)
    tree, count = freeze_tree(params, scfg)
    assert count == 3
    stacked = tree["layers"]["mlp"]["w1"]
    single = tree["layers"]["mlp"]["w2"]
    assert all(fw.tuned is not None for fw in stacked)
    # one tuning shared across the stack: stacked plans must agree on
    # block_n/levels/bucket to ride one lax.scan
    assert len({fw.tuned for fw in stacked}) == 1
    assert all(fw.block_n == fw.tuned.block_n for fw in stacked)
    assert single.tuned is not None
    assert single.tuned.predicted_us <= single.tuned.default_predicted_us
