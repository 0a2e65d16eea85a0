"""The expert layer that holds a share of the experts, and latent
attention, at a small Moonlight-shaped size on the CPU: d 64, 16 experts of
which 8 are held, top-3 over all 16, kv_lora 32, rope 8, nope 16, v 16.

The engine-against-reference comparisons live with the benchmark's
reference (`tests/bench/test_bench_moe.py`); these tests hold the layer
to its own contract: every routed row computed, the shares summing to the
uncut layer, no grid step for an expert without rows, and counters that
match the routes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SpammConfig
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.core import module as spmod
from repro.kernels import ops as kops
from repro.models import moe as moe_mod
from repro.models import transformer as tr
from repro.plans.frozen import FrozenWeight, freeze_experts

D, E, HELD, TOP = 64, 16, 8, 3
MOE = MoEConfig(num_experts=E, top_k=TOP, expert_ff=32, num_shared=2,
                shared_ff=64, scoring="sigmoid", score_bias=True,
                routed_scale=2.446, shared_gate=False, held=HELD)
CFG = ModelConfig(name="moonlight-small", family="moe", num_layers=3,
                  d_model=D, num_heads=4, num_kv_heads=4, head_dim=24,
                  d_ff=128, vocab=256, rope_theta=50000.0, first_dense=1,
                  mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=16),
                  moe=MOE)
TILE = 16
HIGHEST = jax.default_matmul_precision("highest")


def _params(cfg=MOE, seed=0):
    p = moe_mod.moe_params(jax.random.key(seed), cfg, D, jnp.float32)
    p["score_bias"] = 0.01 * jax.random.normal(jax.random.key(seed + 1),
                                               (cfg.num_experts,))
    return p


def _x(t=96, seed=2):
    return jax.random.normal(jax.random.key(seed), (1, t, D), jnp.float32)


def _dense_share(p, x, cfg):
    """Each held expert over every token, weighted by its routed weight
    (zero where not routed): the layer's equation with no layout."""
    xt = x.reshape(-1, D)
    ids, w, _ = moe_mod.route(p, xt, cfg)
    y = jnp.zeros_like(xt)
    for e in range(cfg.held_experts):
        we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        h = jax.nn.silu(xt @ p["w1"][e]) * (xt @ p["w3"][e])
        y = y + we[:, None] * (h @ p["w2"][e])
    return y, ids


def _shared(p, x):
    xt = x.reshape(-1, D)
    s = p["shared"]
    return (jax.nn.silu(xt @ s["w1"]) * (xt @ s["w3"])) @ s["w2"]


def _frozen(p, tile=TILE, backend="interpret"):
    sc = SpammConfig(enable=True, tau=0.0, tile=tile, backend=backend)
    fz = {}
    for name in ("w1", "w3", "w2"):
        fws = [[FrozenWeight.build(np.asarray(p[name][e]), 0.0, tile=tile,
                                   backend=backend)
                for e in range(p[name].shape[0])]]
        fz[name] = freeze_experts(fws)[0]
    return spmod.SpammContext(sc), fz


@pytest.mark.parametrize("gated", [False, True])
def test_share_layer_computes_every_routed_row(gated):
    p = _params()
    x = _x()
    kw = {}
    if gated:
        kw["spamm_cfg"], kw["frozen"] = _frozen(p)
    with HIGHEST:
        y, out = moe_mod.moe_share(p, x, MOE, "silu", **kw)
        want, ids = _dense_share(p, x, MOE)
        want = want + _shared(p, x)
    np.testing.assert_array_equal(np.asarray(out["routes"]), np.asarray(ids))
    # a summation order apart (tile by tile against one product): f32 ulps
    np.testing.assert_allclose(np.asarray(y).reshape(-1, D),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_shares_sum_to_the_uncut_layer():
    """Each half of the experts held in turn (the other half's ids renamed
    to 0-7 by rolling the router's columns), the shared experts counted
    once: the parts add up to the layer that holds all 16."""
    full = dataclasses.replace(MOE, held=0)
    p = _params(full)
    x = _x()
    half = {"router": p["router"], "score_bias": p["score_bias"],
            "shared": p["shared"]}
    a = dict(half, **{n: p[n][:HELD] for n in ("w1", "w3", "w2")})
    b = dict(half, **{n: p[n][HELD:] for n in ("w1", "w3", "w2")},
             router=jnp.roll(p["router"], -HELD, axis=1),
             score_bias=jnp.roll(p["score_bias"], -HELD))
    with HIGHEST:
        ya, _ = moe_mod.moe_share(a, x, MOE, "silu")
        yb, _ = moe_mod.moe_share(b, x, MOE, "silu")
        whole, _ = moe_mod.moe_share(p, x, full, "silu")
        sh = _shared(p, x).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(ya + yb - sh), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


def test_skewed_routing_drops_nothing():
    """Every token routed to held expert 0 (and two more): no capacity, no
    drop, the same sum."""
    p = _params()
    p["score_bias"] = p["score_bias"].at[0].add(10.0).at[1].add(5.0)
    x = _x(t=200)
    spamm, fz = _frozen(p)
    with HIGHEST:
        y, out = moe_mod.moe_share(p, x, MOE, "silu", spamm_cfg=spamm,
                                   frozen=fz)
        want, ids = _dense_share(p, x, MOE)
        want = want + _shared(p, x)
    assert bool(jnp.all(jnp.any(ids == 0, axis=-1)))
    routed, busiest, empty, tiles, dropped = np.asarray(out["counts"])
    assert busiest == 200 and dropped == 0
    np.testing.assert_allclose(np.asarray(y).reshape(-1, D),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_counters_match_the_routes():
    p = _params()
    x = _x()
    spamm, fz = _frozen(p)
    _, out = moe_mod.moe_share(p, x, MOE, "silu", spamm_cfg=spamm, frozen=fz)
    ids = np.asarray(out["routes"])
    counts = np.bincount(ids[ids < HELD], minlength=HELD)
    tiles = int(np.sum(-(-counts // TILE)))
    assert np.asarray(out["counts"]).tolist() == [
        int(counts.sum()), int(counts.max()), int(np.sum(counts == 0)),
        tiles, 0]


def test_an_expert_with_no_rows_runs_no_grid_step(monkeypatch):
    """Only the live row tiles' steps of the expert kernel carry flags, and
    no step reads a block of an expert that holds no row: the steps past
    the live tiles repeat the last live step with no flag."""
    p = _params()
    x = _x(t=40)
    p["score_bias"] = p["score_bias"].at[3].add(-10.0).at[5].add(-10.0)
    spamm, fz = _frozen(p)
    calls = []
    bk = kops.get_backend("interpret")
    real = bk.matmul_worklist_experts

    def spy(a, b, si, sj, sk, sb, fl, tile, kb, out_dtype):
        # the kernel call traces inside the chunk loop: read its tables
        # when they run
        nkb = a.shape[1] // (tile * kb)
        per = sb.shape[0] // (a.shape[0] // tile)
        jax.debug.callback(
            lambda sb, fl: calls.append((np.asarray(sb) // nkb,
                                         np.asarray(fl), per)), sb, fl)
        return real(a, b, si, sj, sk, sb, fl, tile, kb, out_dtype)

    monkeypatch.setitem(kops.BACKENDS, "interpret",
                        dataclasses.replace(bk, matmul_worklist_experts=spy))
    _, out = moe_mod.moe_share(p, x, MOE, "silu", spamm_cfg=spamm, frozen=fz)
    ids = np.asarray(out["routes"])
    empty = set(range(HELD)) - set(ids[ids < HELD].tolist())
    assert {3, 5} <= empty
    live = int(np.asarray(out["counts"])[3])
    assert len(calls) == 3                      # w1, w3, w2: one chunk
    for expert, flags, per in calls:
        steps = live * per
        assert not flags[steps:].any()
        assert (expert[steps:] == expert[steps - 1]).all()
        assert set(expert.tolist()) == set(range(HELD)) - empty


def test_absorbed_latent_decode_matches_decompressed_attention():
    """A token decoded against the latent cache (wkv_b absorbed into the
    query and the output) gives what the decompressed prefill gives at
    that position."""
    key = jax.random.key(4)
    p = tr.mla_params(key, CFG, jnp.float32)
    p["kv_ln"] = 0.1 * jax.random.normal(jax.random.key(5), (32,))
    s = 24
    x = jax.random.normal(jax.random.key(6), (2, s, D), jnp.float32)
    pcfg = tr.ParallelConfig(compute_dtype="float32", attn_q_chunk=8,
                             attn_kv_chunk=8)
    ctx = tr.NetCtx(mesh=None)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (2, s))
    with HIGHEST:
        full, lat = tr.mla_layer(p, x, CFG, pcfg, ctx, pos)
        cache = jnp.pad(lat[:, :s - 1], ((0, 0), (0, 1), (0, 0)))
        one, cache = tr.mla_decode(p, x[:, s - 1:], cache, s - 1, CFG)
    np.testing.assert_allclose(np.asarray(one[:, 0]),
                               np.asarray(full[:, s - 1]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(lat),
                               rtol=1e-6, atol=1e-6)


def test_a_prefill_too_large_for_one_kernel_call_runs_in_groups(monkeypatch):
    """Where a frozen plan's step tables at the wave's rows would overflow
    the work-list kernel's SMEM, the wave's prefill runs over groups of
    whole requests: the same tokens, logits and routes as one call."""
    from repro.kernels import common
    from repro.launch.serve import init_params, make_engine
    from repro.serving.engine import Request

    cfg = CFG
    params = init_params(cfg, 0)
    toks = np.random.default_rng(3).integers(1, 256, size=(4, 32)).astype(
        np.int32)
    sc = SpammConfig(enable=True, tau=0.0, tile=TILE, backend="interpret")

    def wave():
        eng = make_engine(cfg, params, max_len=36, spamm_cfg=sc)
        outs = eng.generate([Request(prompt=t, max_new_tokens=4)
                             for t in toks])
        return eng, np.stack(outs)

    one, outs1 = wave()
    assert one._prefill_groups(4, 32) == 1
    assert {"freeze", "freeze_experts"} <= one.obs.tracer.span_names()
    # 40 steps of tables: 8 row tiles of 8 steps do not fit, 4 do
    monkeypatch.setattr(common, "SMEM_BYTES", common.SMEM_RESERVE + 40 * 16)
    two, outs2 = wave()
    assert two._prefill_groups(4, 32) == 2
    np.testing.assert_array_equal(outs1, outs2)
    np.testing.assert_allclose(np.asarray(two.first_logits),
                               np.asarray(one.first_logits), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(two.moe_routes[0]),
                                  np.asarray(one.moe_routes[0]))
