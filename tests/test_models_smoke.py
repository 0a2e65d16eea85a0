"""Per-architecture smoke tests (assigned deliverable f): REDUCED same-family
configs, one forward/train step on CPU, output shapes + no NaNs; decode path
consistency against the full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import ZOO_IDS, ParallelConfig, TrainConfig, get_config
from repro.models import model as M
from repro.models.transformer import NetCtx
from repro.optim.adamw import AdamW

PCFG = ParallelConfig(
    compute_dtype="float32", param_dtype="float32", remat="none",
    attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=32, decode_seq_shard=False,
)
B, S = 2, 64


def _ctx():
    from repro.launch.mesh import make_mesh  # auto-sharded axes

    return NetCtx(mesh=make_mesh((1, 1), ("data", "model")))


def _inputs(cfg, key=1):
    if cfg.frontend:
        return {"embeds": 0.5 * jax.random.normal(
            jax.random.key(key), (B, S, cfg.d_model), jnp.float32)}
    return {"tokens": jax.random.randint(jax.random.key(key), (B, S), 0,
                                         cfg.vocab)}


@pytest.mark.parametrize("arch", ZOO_IDS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    ctx = _ctx()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    inp = _inputs(cfg)
    batch = dict(inp, labels=jnp.ones((B, S), jnp.int32))

    h, aux = jax.jit(lambda p, b: M.forward_hidden(cfg, PCFG, ctx, p, b))(
        params, inp)
    assert h.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h)))

    opt = AdamW(TrainConfig(total_steps=10, warmup=1))
    step = jax.jit(M.make_train_step(cfg, PCFG, ctx, opt))
    p2, o2, met = step(params, opt.init(params), batch, jnp.int32(0))
    assert bool(jnp.isfinite(met["loss"]))
    # params actually changed
    delta = sum(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    )
    assert delta > 0


@pytest.mark.parametrize("arch", ZOO_IDS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:  # avoid capacity drops confounding the check
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    ctx = _ctx()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    inp = _inputs(cfg)

    h, _ = jax.jit(lambda p, b: M.forward_hidden(cfg, PCFG, ctx, p, b))(
        params, inp)
    h_last = L.rms_norm(h[:, -1], params["final_norm"], cfg.norm_eps)
    lg_ref = (h_last @ params["unembed"]["kernel"]).astype(jnp.float32)

    prefill = jax.jit(M.make_prefill_step(cfg, PCFG, ctx))
    decode = jax.jit(M.make_decode_step(cfg, PCFG, ctx))
    if cfg.frontend:
        b1 = {"embeds": inp["embeds"][:, : S - 1]}
        last = inp["embeds"][:, S - 1 : S]
    else:
        b1 = {"tokens": inp["tokens"][:, : S - 1]}
        last = inp["tokens"][:, S - 1 : S]
    cache, _, _ = prefill(params, b1)

    def grow_kv(path, t):
        keys = [getattr(k, "key", None) for k in path]
        # MHA K/V (..., S, Hk, hd); latent attention's (..., S, C)
        ax = {"k": -3, "v": -3, "lat": -2}.get(keys[-1] if keys else None)
        if ax is not None and t.shape[ax] == S - 1:
            pad = [(0, 0)] * t.ndim
            pad[ax] = (0, 1)
            return jnp.pad(t, pad)
        return t

    cache = jtu.tree_map_with_path(grow_kv, cache)
    lg_dec, _, _ = decode(params, last, cache, jnp.int32(S - 1))
    rel = float(jnp.max(jnp.abs(lg_dec - lg_ref))) / (
        float(jnp.max(jnp.abs(lg_ref))) + 1e-9)
    assert rel < 5e-4, rel


def test_spamm_enabled_forward_matches_dense_at_tau0():
    """The paper's technique as a config switch: τ=0 must be bit-compatible
    with the dense path (same GEMMs, gated at 100% valid)."""
    from repro.configs import SpammConfig

    cfg = get_config("codeqwen1.5-7b").reduced()
    ctx = _ctx()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    inp = _inputs(cfg)
    batch = dict(inp, labels=jnp.ones((B, S), jnp.int32))
    l0, _ = jax.jit(lambda p, b: M.loss_fn(cfg, PCFG, ctx, p, b))(params, batch)
    sp = SpammConfig(enable=True, tau=0.0, tile=32, backend="jnp")
    l1, _ = jax.jit(
        lambda p, b: M.loss_fn(cfg, PCFG, ctx, p, b, spamm_cfg=sp)
    )(params, batch)
    assert abs(float(l0) - float(l1)) < 1e-4, (float(l0), float(l1))


def test_spamm_moe_bmm_forward_matches_dense_at_tau0():
    """Batched spamm_bmm execution of the MoE grouped FFN (per-expert weight
    plans) must also be exact at τ=0."""
    from repro.configs import SpammConfig

    cfg = get_config("qwen2-moe-a2.7b").reduced()
    ctx = _ctx()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    inp = _inputs(cfg)
    h0, _ = M.forward_hidden(cfg, PCFG, ctx, params, inp)
    sp = SpammConfig(enable=True, tau=0.0, tile=16, backend="jnp",
                     moe_bmm=True)
    h1, _ = M.forward_hidden(cfg, PCFG, ctx, params, inp, spamm_cfg=sp)
    assert float(jnp.max(jnp.abs(h0 - h1))) < 1e-4
