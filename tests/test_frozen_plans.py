"""Frozen-plan runtime — ISSUE 4 tentpole coverage.

The frozen-weight path (plans as jit inputs) must be bit-identical to the
eager plan()+execute() pipeline under jit and nested jit; the PlanStore must
hit/miss/refuse correctly (content addressing + version/backend guards); a
frozen-weight trace must contain zero weight-side get-norm calls and zero
dense-bitmap sorts (monkeypatch guard); and the serving engine must
warm-start from a precomputed store with store misses only on first
population, reproducing the same outputs.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ck
from repro.configs import ParallelConfig, SpammConfig, get_config
from repro.core import plan as pl
from repro.core.module import SpammContext
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.launch.mesh import make_ctx, make_host_mesh
from repro.models import model as M
from repro.plans import (FrozenWeight, PLAN_FORMAT_VERSION, PlanStore,
                         PlanStoreError, fingerprint, freeze_tree,
                         iter_gated_weights, populate, stack_plans)
from repro.serving.engine import Engine, Request

PCFG = ParallelConfig(
    compute_dtype="float32", param_dtype="float32", remat="none",
    attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=32, decode_seq_shard=False,
)


def _decay(m, n, seed, scale=0.4):
    rng = np.random.default_rng(seed)
    d = np.abs(np.arange(m)[:, None] - np.arange(n)[None, :])
    base = (scale / (d ** 0.5 + 1)).astype(np.float32)
    return jnp.asarray(base * rng.standard_normal((m, n)).astype(np.float32))


TAU = 4.0  # gates a real (partial) fraction on _decay operands at tile=32


# ---------------------------------------------------------------------------
# frozen path ≡ eager plan+execute
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("block_n", [1, 2])
@pytest.mark.parametrize("levels", [0, 1])
def test_frozen_bit_identical_to_eager_under_jit(backend, block_n, levels):
    a, b = _decay(96, 128, 0), _decay(128, 192, 1)
    ap = pl.pad_to_tile(a, 32)
    bp = pl.pad_to_tile(b, 32, 32 * block_n)
    p_e = pl.plan(ap, bp, TAU, tile=32, block_n=block_n, backend=backend,
                  levels=levels)
    want = pl.execute(p_e, ap, bp)
    assert 0 < int(p_e.valid_tiles) < p_e.total_tiles  # a real partial gate

    fw = FrozenWeight.build(b, TAU, tile=32, block_n=block_n, levels=levels,
                            backend=backend)
    fp = fw.for_rows(ap.shape[0] // 32)

    # eager frozen
    got = pl.execute(pl.plan(ap, frozen_weight=fp), ap, bp)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    # jitted: the FrozenPlan is a jit ARGUMENT (a pytree of arrays)
    @jax.jit
    def run(x, w, f):
        p = pl.plan(x, frozen_weight=f)
        return pl.execute(p, x, w), p.valid_tiles

    got_j, vt = run(ap, bp, fp)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got_j))
    assert int(vt) == int(p_e.valid_tiles)

    # nested jit
    @jax.jit
    def run2(x, w, f):
        return run(x, w, f)[0]

    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(run2(ap, bp, fp)))


def test_frozen_edge_cases():
    b = _decay(64, 64, 2)
    # all-pruned activation (zeros) → zero output, correct shape
    fw = FrozenWeight.build(b, 0.5, tile=32, backend="interpret")
    z = jnp.zeros((64, 64), jnp.float32)
    c = pl.execute(pl.plan(z, frozen_weight=fw.for_rows(2)), z, b)
    np.testing.assert_array_equal(np.asarray(c), np.zeros((64, 64)))
    # fully-pruned weight (τ > 0 and zero weight): empty kj list
    fw0 = FrozenWeight.build(jnp.zeros((64, 64)), 0.5, tile=32,
                             backend="interpret")
    assert fw0.num_kj == 0
    x = _decay(64, 64, 3)
    c0 = pl.execute(pl.plan(x, frozen_weight=fw0.for_rows(2)), x,
                    jnp.zeros((64, 64)))
    np.testing.assert_array_equal(np.asarray(c0), np.zeros((64, 64)))
    # τ ≤ 0: everything passes, == dense
    fwn = FrozenWeight.build(b, 0.0, tile=32, backend="jnp")
    p = pl.plan(x, frozen_weight=fwn.for_rows(2))
    assert int(p.valid_tiles) == p.total_tiles


def test_frozen_plan_rejects_wrong_row_grid():
    fw = FrozenWeight.build(_decay(64, 64, 4), TAU, tile=32, backend="jnp")
    with pytest.raises(ValueError, match="specialized"):
        pl.plan(_decay(96, 64, 5), frozen_weight=fw.for_rows(2))


def test_frozen_weight_carries_its_own_tau():
    fw = FrozenWeight.build(_decay(64, 64, 6), TAU, tile=32, backend="jnp")
    with pytest.raises(ValueError, match="its own tau"):
        pl.plan(_decay(64, 64, 7), None, TAU, frozen_weight=fw.for_rows(2))


def test_stacked_frozen_plans_ride_a_scan():
    """Per-layer plans stacked to one common bucket ride lax.scan as xs and
    gate each layer with ITS weight's norms — the engine's scan shape."""
    x = _decay(64, 64, 42)
    fws = [FrozenWeight.build(_decay(64, 64, s), 2.0, tile=32,
                              backend="interpret") for s in (7, 8, 9)]
    bucket = max(pl._bucket(2 * fw.num_kj) for fw in fws)
    stacked = stack_plans([fw.for_rows(2, min_steps=bucket) for fw in fws])

    @jax.jit
    def scan_counts(stk):
        def body(c, f):
            return c, pl.plan(x, frozen_weight=f).valid_tiles

        return jax.lax.scan(body, 0, stk)[1]

    counts = scan_counts(stacked)
    for i, fw in enumerate(fws):
        pe = pl.plan(x, None, 2.0, norm_b=fw.norm_b, tile=32,
                     backend="interpret")
        assert int(counts[i]) == int(pe.valid_tiles)


# ---------------------------------------------------------------------------
# monkeypatch guard: nothing weight-side is recomputed inside the trace
# ---------------------------------------------------------------------------

def _counting_backend(name, calls):
    orig = kops.BACKENDS[name]

    def norms(x, tile, use_mxu=False):
        calls.append(tuple(x.shape))
        return orig.norms(x, tile, use_mxu=use_mxu)

    return dataclasses.replace(orig, norms=norms)


def test_no_getnorm_and_no_dense_sort_in_frozen_trace(monkeypatch):
    """Tracing a frozen-weight product runs ZERO get-norm calls when the
    activation norms are supplied, only activation-shaped ones otherwise,
    and never touches the dense-bitmap sort (`spamm_compact_ref`)."""
    a, b = _decay(96, 64, 10), _decay(64, 128, 11)
    fw = FrozenWeight.build(b, TAU, tile=32, backend="interpret")
    fp = fw.for_rows(3)

    calls = []
    monkeypatch.setitem(kops.BACKENDS, "interpret",
                        _counting_backend("interpret", calls))

    def boom(*a_, **k_):
        raise AssertionError("dense-bitmap sort inside a frozen trace")

    monkeypatch.setattr(ref, "spamm_compact_ref", boom)

    @jax.jit
    def run(x, w, f):
        return pl.execute(pl.plan(x, frozen_weight=f), x, w)

    run(a, b, fp)  # traces here
    assert calls == [(96, 64)], calls  # the activation gate, nothing else

    calls.clear()
    na = kops.BACKENDS["interpret"].norms(a, 32)
    calls.clear()

    @jax.jit
    def run_prenormed(x, w, f, n):
        return pl.execute(pl.plan(x, frozen_weight=f, norm_a=n), x, w)

    run_prenormed(a, b, fp, na)
    assert calls == [], calls  # zero get-norm ops in the traced graph


# ---------------------------------------------------------------------------
# PlanStore: hit / miss / invalidation / refusal
# ---------------------------------------------------------------------------

def _mk_fw(b, **kw):
    cfg = dict(tau=TAU, tile=32, block_n=1, levels=1, backend="jnp")
    cfg.update(kw)
    return FrozenWeight.build(b, cfg.pop("tau"), weight_hash=fingerprint(b),
                              **cfg), cfg


def test_store_roundtrip_hit_and_config_invalidation(tmp_path):
    b = _decay(64, 96, 20)
    st = PlanStore(str(tmp_path))
    fw, _ = _mk_fw(b)
    st.put(fw)
    base = dict(tau=TAU, tile=32, block_n=1, levels=1, backend="jnp")

    got = st.get(fingerprint(b), **base)
    assert got is not None and st.hits == 1 and st.misses == 0
    np.testing.assert_array_equal(np.asarray(got.nbmax), np.asarray(fw.nbmax))
    np.testing.assert_array_equal(np.asarray(got.kj_k), np.asarray(fw.kj_k))
    for l in range(len(fw.levels)):
        np.testing.assert_array_equal(np.asarray(got.levels[l]),
                                      np.asarray(fw.levels[l]))
    # loaded artifact plans identically to the freshly built one
    x = _decay(64, 64, 21)
    p1 = pl.plan(x, frozen_weight=fw.for_rows(2))
    p2 = pl.plan(x, frozen_weight=got.for_rows(2))
    np.testing.assert_array_equal(np.asarray(p1.mask), np.asarray(p2.mask))

    # the weight changing is a miss (content addressing) ...
    b2 = b.at[0, 0].add(1.0)
    assert st.get(fingerprint(b2), **base) is None
    # ... and so is ANY config field changing (incl. the get-norm variant)
    for field, val in [("tau", TAU * 2), ("tile", 16), ("block_n", 2),
                       ("levels", 0), ("backend", "interpret"),
                       ("use_mxu", True)]:
        assert st.get(fingerprint(b), **{**base, field: val}) is None, field


def test_store_refuses_version_and_backend_mismatch(tmp_path):
    import json

    b = _decay(64, 64, 22)
    st = PlanStore(str(tmp_path))
    fw, _ = _mk_fw(b)
    key = st.put(fw)
    mpath = os.path.join(str(tmp_path), key, "manifest.json")
    base = dict(tau=TAU, tile=32, block_n=1, levels=1, backend="jnp")

    with open(mpath) as f:
        man = json.load(f)
    man["format_version"] = PLAN_FORMAT_VERSION + 1
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(PlanStoreError, match="format version"):
        st.get(fingerprint(b), **base)

    man["format_version"] = PLAN_FORMAT_VERSION
    man["backend"] = "not-a-backend"
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(PlanStoreError, match="not registered"):
        st.get(fingerprint(b), **base)


def test_weight_plan_cache_is_memory_tier_above_store(tmp_path):
    b = _decay(64, 64, 23)
    st = PlanStore(str(tmp_path))
    cache = pl.WeightPlanCache(store=st)
    kw = dict(tau=TAU, tile=32, levels=1, backend="jnp")
    fw1 = cache.frozen_weight(b, **kw)
    assert cache.frozen_misses == 1 and st.misses == 1 and len(st) == 1
    fw2 = cache.frozen_weight(b, **kw)           # memory hit
    assert fw2 is fw1 and cache.frozen_hits == 1 and st.hits == 0
    cache2 = pl.WeightPlanCache(store=st)        # cold memory, warm store
    fw3 = cache2.frozen_weight(b, **kw)
    assert st.hits == 1 and st.misses == 1       # loaded, not rebuilt
    np.testing.assert_array_equal(np.asarray(fw3.nbmax), np.asarray(fw1.nbmax))


# ---------------------------------------------------------------------------
# engine integration: warm start, parity, phase-tagged telemetry
# ---------------------------------------------------------------------------

def _mk_engine(params, cfg, ctx, sc, **kw):
    return Engine(cfg, PCFG, ctx, params, max_len=64, spamm_cfg=sc, **kw)


def test_engine_frozen_prefill_matches_legacy_and_walks_gated_weights():
    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="jnp", levels=1)
    rng = np.random.default_rng(0)
    reqs = lambda: [Request(prompt=rng.integers(1, cfg.vocab, size=24).astype(
        np.int32), max_new_tokens=4) for _ in range(2)]
    rng = np.random.default_rng(0)
    r_legacy = reqs()
    rng = np.random.default_rng(0)
    r_frozen = reqs()
    outs_l = _mk_engine(params, cfg, ctx, sc, freeze_plans=False).generate(
        r_legacy)
    eng = _mk_engine(params, cfg, ctx, sc)
    outs_f = eng.generate(r_frozen)
    for a, b in zip(outs_l, outs_f):
        np.testing.assert_array_equal(a, b)
    # the walker found the gated GEMM weights (4 attn + 2 gelu_mlp weights)
    paths = {p[-2:] for p, _ in iter_gated_weights(params)}
    assert paths == {("mix", "wq"), ("mix", "wk"), ("mix", "wv"),
                     ("mix", "wo"), ("mlp", "w1"), ("mlp", "w2")}
    sp = r_frozen[0].out["spamm"]
    assert sp["gated_gemms"] > 0
    assert sp["decode_gated_gemms"] > 0          # decode taps, tagged apart
    assert sp["valid_fraction"] is not None
    assert sp["decode_valid_fraction"] is not None


def test_engine_warm_starts_from_precomputed_store(tmp_path, monkeypatch):
    """precompute CLI path → fresh engine with --plan-store: same outputs,
    store misses only during population, and the frozen-weight warm start
    runs ZERO get-norm calls on weight shapes (the guard satellite, at the
    engine level) and never the dense-bitmap sort."""
    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="interpret")
    store = PlanStore(str(tmp_path))
    n = populate(store, params, sc)              # the offline pass
    expected = sum(
        int(np.prod(w.shape[:-2], dtype=np.int64)) if w.ndim > 2 else 1
        for _, w in iter_gated_weights(params))
    assert n == expected == 6 * cfg.num_layers
    assert store.misses == n and store.hits == 0 and len(store) > 0

    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, size=24).astype(np.int32)
               for _ in range(2)]
    mk_reqs = lambda: [Request(prompt=p, max_new_tokens=3) for p in prompts]

    baseline = _mk_engine(params, cfg, ctx, sc).generate(mk_reqs())

    # weight shapes in this reduced config, to tell apart from activations
    weight_shapes = {tuple(w.shape[-2:]) for _, w in
                     iter_gated_weights(params)}
    calls = []
    monkeypatch.setitem(kops.BACKENDS, "interpret",
                        _counting_backend("interpret", calls))

    def boom(*a_, **k_):
        raise AssertionError("dense-bitmap sort in a frozen-weight engine")

    monkeypatch.setattr(ref, "spamm_compact_ref", boom)

    store2 = PlanStore(str(tmp_path))
    warm_reqs = mk_reqs()
    eng = _mk_engine(params, cfg, ctx, sc, plan_store=store2)
    warm = eng.generate(warm_reqs)
    for a, b in zip(baseline, warm):
        np.testing.assert_array_equal(a, b)
    assert store2.misses == 0 and store2.hits == n  # warm: loads only
    assert not any(s in weight_shapes for s in calls), calls
    sp = warm_reqs[0].out["spamm"]
    assert sp["plan_store_hits"] == n and sp["plan_store_misses"] == 0
    # store counters are per-WAVE deltas: a second wave never re-touches the
    # store (frozen plans cached in memory) and must report 0/0
    reqs2 = mk_reqs()
    eng.generate(reqs2)
    sp2 = reqs2[0].out["spamm"]
    assert sp2["plan_store_hits"] == 0 and sp2["plan_store_misses"] == 0


def test_engine_frozen_parity_on_hybrid_arch():
    """Hybrid (rec, rec, attn) stacks thread frozen plans through the
    grouped scan: only the attn sub-layer's projections and every
    sub-layer's MLP carry plans; rec mixers have no gated GEMMs."""
    cfg = get_config("recurrentgemma-9b").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(1))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="jnp")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab, size=20).astype(np.int32)
               for _ in range(2)]
    mk = lambda: [Request(prompt=p, max_new_tokens=3) for p in prompts]
    outs_l = _mk_engine(params, cfg, ctx, sc, freeze_plans=False).generate(mk())
    outs_f = _mk_engine(params, cfg, ctx, sc).generate(mk())
    for a, b in zip(outs_l, outs_f):
        np.testing.assert_array_equal(a, b)


def test_freeze_tree_covers_hybrid_groups():
    """Reduced recurrentgemma is one (rec, rec, attn) group with no tail:
    only the attn sub-layer contributes wq..wo, every sub-layer an MLP."""
    cfg = get_config("recurrentgemma-9b").reduced()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.1, tile=16, backend="jnp")
    tree, count = freeze_tree(params, sc)
    assert "groups" in tree
    attn = tree["groups"]["l2"]["mix"]
    assert set(attn) == {"wq", "wk", "wv", "wo"}
    assert isinstance(attn["wq"], list)          # stacked → per-layer list
    assert set(tree["groups"]["l0"]) == {"mlp"}  # rec sub-layer: MLP only
    assert count == 4 + 3 * len(tree["groups"])  # 4 attn + 3 SwiGLU per sub


# ---------------------------------------------------------------------------
# checkpoint pointer round-trip
# ---------------------------------------------------------------------------

def test_checkpoint_plan_store_pointer_roundtrip(tmp_path):
    store_dir = tmp_path / "plans"
    ckpt_dir = str(tmp_path / "ckpt")
    b = _decay(64, 64, 30)
    st = PlanStore(str(store_dir))
    fw, _ = _mk_fw(b)
    st.put(fw)

    ck.save(ckpt_dir, 10, {"w": jnp.ones(3)}, plan_store=st)
    ptr = ck.plan_store_pointer(ckpt_dir, 10)
    assert ptr == {"path": os.path.abspath(str(store_dir)),
                   "format_version": PLAN_FORMAT_VERSION}
    st2 = ck.open_plan_store(ckpt_dir, 10)
    assert st2 is not None and len(st2) == 1
    got = st2.get(fingerprint(b), tau=TAU, tile=32, block_n=1, levels=1,
                  backend="jnp")
    assert got is not None                        # restored server finds plans

    # checkpoints without a pointer stay None (back-compat)
    ck.save(ckpt_dir, 20, {"w": jnp.ones(3)})
    assert ck.plan_store_pointer(ckpt_dir, 20) is None
    assert ck.open_plan_store(ckpt_dir, 20) is None


# ---------------------------------------------------------------------------
# train-step telemetry export
# ---------------------------------------------------------------------------

def test_train_loop_exports_spamm_stats(tmp_path):
    from repro.configs.base import TrainConfig
    from repro.train.loop import train

    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    tcfg = TrainConfig(total_steps=2, warmup=1, ckpt_every=0,
                       ckpt_dir=str(tmp_path))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="jnp")
    res = train(cfg, PCFG, tcfg, ctx, global_batch=2, seq_len=32,
                spamm_cfg=sc, log_every=0)
    assert len(res.spamm_stats) == 2
    for s in res.spamm_stats:
        assert s["gated_gemms"] > 0
        assert s["valid_fraction"] is not None
        assert 0.0 < s["valid_fraction"] <= 1.0
    # without SpAMM the export stays empty
    res0 = train(cfg, PCFG, tcfg, ctx, global_batch=2, seq_len=32,
                 log_every=0)
    assert res0.spamm_stats == []


def test_store_refuses_pre_dtype_legacy_root(tmp_path):
    """ISSUE 6 regression: a store root populated under the pre-dtype
    format (version < 2: artifact dirs but no STORE_FORMAT.json marker)
    must refuse at OPEN time with PlanStoreError — dtype is part of every
    key now, so the legacy artifacts would otherwise read as clean misses
    and a warm start would silently refreeze everything."""
    import json
    import shutil

    # fabricate a legacy root: one artifact dir, no marker
    legacy = tmp_path / "legacy"
    art = legacy / "deadbeefdeadbeef"
    art.mkdir(parents=True)
    with open(art / "manifest.json", "w") as f:
        json.dump({"format_version": PLAN_FORMAT_VERSION - 1}, f)
    with pytest.raises(PlanStoreError, match="predates compute-dtype"):
        PlanStore(str(legacy))

    # a marker with the wrong version refuses too
    vers = tmp_path / "versioned"
    vers.mkdir()
    with open(vers / "STORE_FORMAT.json", "w") as f:
        json.dump({"format_version": PLAN_FORMAT_VERSION - 1}, f)
    with pytest.raises(PlanStoreError, match="fresh root"):
        PlanStore(str(vers))

    # fresh roots self-mark and reopen cleanly (crash-leftover .tmp_* dirs
    # don't count as artifacts)
    fresh = tmp_path / "fresh"
    st = PlanStore(str(fresh))
    assert (fresh / "STORE_FORMAT.json").is_file()
    (fresh / ".tmp_junk").mkdir()
    shutil.rmtree(str(fresh / ".tmp_junk"))
    st2 = PlanStore(str(fresh))
    b = _decay(64, 64, 30)
    fw, _ = _mk_fw(b)
    st2.put(fw)
    # and a third open of the now-populated, marked root still succeeds
    assert len(PlanStore(str(fresh))) == 1


# ---------------------------------------------------------------------------
# k-blocked frozen plans: kb k-tiles a step, bit-identical to kb = 1
# ---------------------------------------------------------------------------

def _kb_operands():
    """A (96, 256) activation whose middle row tile is zero (its segments
    have no surviving k-tile) and a (256, 128) weight: gk = 8 at tile 32."""
    a = np.asarray(_decay(96, 256, 50)).copy()
    a[32:64] = 0.0
    return jnp.asarray(a), _decay(256, 128, 51)


def _run_frozen(x, w, fp):
    @jax.jit
    def run(x_, w_, f):
        p = pl.plan(x_, frozen_weight=f)
        return pl.execute(p, x_, w_), p.mask, p.nvalid, p.work.step_flags

    return run(x, w, fp)


def _check_empty_segments(fp, flags):
    """Every segment with no active step still writes its tile: INIT|FLUSH
    and no ACC on its last step."""
    flags = np.asarray(flags)
    last = np.asarray(fp.seg_last)
    acc = (flags & pl.STEP_ACC) != 0
    for e in np.unique(last):
        seg = last == e
        if not acc[seg].any():
            assert flags[e] == pl.STEP_INIT | pl.STEP_FLUSH
    return int((~np.array([acc[last == e].any()
                           for e in np.unique(last)])).sum())


@pytest.mark.parametrize("kb", [2, 4, 8])
def test_frozen_kb_gates_and_multiplies_like_kb1(kb):
    x, w = _kb_operands()
    fw = FrozenWeight.build(w, TAU, tile=32, backend="interpret")
    one, blk = fw.for_rows(3, kb=1), fw.for_rows(3, kb=kb)
    assert (one.kb, blk.kb) == (1, kb)
    assert blk.num_steps < one.num_steps
    c1, m1, n1, _ = _run_frozen(x, w, one)
    ck, mk, nk, fk = _run_frozen(x, w, blk)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(n1))
    assert _check_empty_segments(blk, fk) > 0  # the zero row tile's


@pytest.mark.parametrize("kb", [2, 8])
def test_frozen_kb_slice_rows_and_shards_like_kb1(kb):
    """slice_rows at kb > 1 runs a shard's strip bit-identically to kb = 1,
    and shard_by_offsets gives every shard one kb and one bucket."""
    x, w = _kb_operands()
    fw = FrozenWeight.build(w, TAU, tile=32, backend="interpret")
    # strip [1, 3) on a local grid of 3 tiles, clamp-padded with its last
    local = jnp.concatenate([x[32:96], x[64:96]])
    c1 = _run_frozen(local, w, fw.slice_rows(1, 3, gm=3, kb=1))[0]
    sl = fw.slice_rows(1, 3, gm=3, kb=kb)
    ck, _, _, fk = _run_frozen(local, w, sl)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(c1))
    assert _check_empty_segments(sl, fk) > 0
    sh = fw.shard_by_offsets(np.array([0, 1, 3]), width=3)
    assert sh.kb == fw.choose_kb(3) and sh.kb > 1
    for d, (lo, hi) in enumerate(((0, 1), (1, 3))):
        part = jax.tree.map(lambda t: t[d], sh)
        xs = x[lo * 32:hi * 32]
        xs = jnp.concatenate([xs] + [xs[-32:]] * (3 - (hi - lo)))
        want = _run_frozen(xs, w, fw.slice_rows(lo, hi, gm=3, kb=1))[0]
        np.testing.assert_array_equal(
            np.asarray(_run_frozen(xs, w, part)[0]), np.asarray(want))


def test_stack_plans_brings_layers_to_the_smallest_kb():
    """Layers whose kb differ stack at the smallest of them, on one bucket,
    with each layer's tables exactly its weight's own at that kb; the scan
    then gates and multiplies each layer as at kb = 1."""
    x, _ = _kb_operands()
    ws = [_decay(256, 128, s) for s in (60, 61)]
    fws = [FrozenWeight.build(w_, TAU, tile=32, backend="interpret")
           for w_ in ws]
    st = stack_plans([fws[0].for_rows(3, kb=8), fws[1].for_rows(3, kb=2)])
    assert st.kb == 2
    for i, fw in enumerate(fws):
        own = fw.for_rows(3, kb=2, min_steps=st.num_steps)
        layer = jax.tree.map(lambda t: t[i], st)
        for f in ("step_i", "step_j", "step_k", "step_real", "seg_first",
                  "seg_last"):
            np.testing.assert_array_equal(np.asarray(getattr(layer, f)),
                                          np.asarray(getattr(own, f)))
        want = _run_frozen(x, ws[i], fw.for_rows(3, kb=1))[0]
        np.testing.assert_array_equal(
            np.asarray(_run_frozen(x, ws[i], layer)[0]), np.asarray(want))


def test_engine_reports_kb_and_block_fill_per_site():
    """Freezing a row grid sets one `spamm_kb` and one `spamm_block_fill`
    gauge per gated-GEMM site; at tau = 0 every k-block is full."""
    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.0, tile=16, backend="interpret")
    eng = _mk_engine(params, cfg, ctx, sc)
    fps = eng._frozen_for(64)
    reg = eng.obs.registry
    g_kb, g_fill = (reg.gauge(name, labelnames=("site", "gm"))
                    for name in ("spamm_kb", "spamm_block_fill"))
    sites = ["/".join(p) for p, _ in iter_gated_weights(params)]
    assert len(sites) == 6
    for site in sites:
        assert g_fill.value(site=site, gm="4") == 1.0
        node = fps
        for part in site.split("/"):
            node = node[part]
        assert g_kb.value(site=site, gm="4") == node.kb > 1
