"""Checkpoint roundtrip/atomicity/GC + serving-engine behavior."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ck
from repro.configs import ParallelConfig, get_config
from repro.launch.mesh import make_ctx, make_host_mesh
from repro.models import model as M
from repro.serving.engine import Engine, Request

PCFG = ParallelConfig(
    compute_dtype="float32", param_dtype="float32", remat="none",
    attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=32, decode_seq_shard=False,
)


def test_roundtrip_and_gc(tmp_path):
    d = str(tmp_path)
    state = {
        "params": {"a": jnp.arange(12.0).reshape(3, 4),
                   "nested": {"b": jnp.ones((2, 2), jnp.bfloat16)}},
        "step": jnp.int32(7),
    }
    for s in [10, 20, 30, 40]:
        ck.save(d, s, state, keep=2)
    assert ck.all_steps(d) == [30, 40]
    like = jax.eval_shape(lambda: state)
    out = ck.restore(d, 40, like)
    np.testing.assert_array_equal(out["params"]["a"],
                                  np.arange(12.0).reshape(3, 4))
    assert out["params"]["nested"]["b"].dtype == jnp.bfloat16


def test_async_save(tmp_path):
    d = str(tmp_path)
    t = ck.save(d, 5, {"x": jnp.ones(3)}, async_=True)
    t.join()
    assert ck.latest_step(d) == 5


def test_tmp_dirs_never_visible(tmp_path):
    d = str(tmp_path)
    os.makedirs(os.path.join(d, ".tmp_step_99"))  # simulated crash leftovers
    ck.save(d, 1, {"x": jnp.ones(2)})
    assert ck.all_steps(d) == [1]


def test_engine_greedy_matches_manual_decode():
    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    eng = Engine(cfg, PCFG, ctx, params, max_len=96)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=24).astype(np.int32)
               for _ in range(3)]
    outs = eng.generate([Request(prompt=p, max_new_tokens=6) for p in prompts])
    assert all(len(o) == 6 for o in outs)

    # manual greedy for request 0 must match slot 0 of the batch exactly
    # (batch composition must not change a slot's tokens)
    outs_single = eng.generate([Request(prompt=prompts[0], max_new_tokens=6)])
    np.testing.assert_array_equal(outs[0], outs_single[0])


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_engine_step_dot_precision(compute_dtype):
    """f32 compute traces every dot of the compiled steps at HIGHEST (a TPU
    runs a DEFAULT f32 dot as one bf16 pass); other compute dtypes leave
    the precision to the caller."""
    cfg = get_config("musicgen-large").reduced()
    pcfg = dataclasses.replace(PCFG, compute_dtype=compute_dtype)
    params = M.init_params(cfg, pcfg, jax.random.key(0))
    eng = Engine(cfg, pcfg, make_ctx(make_host_mesh()), params, max_len=32)
    toks = jnp.zeros((2, 16), jnp.int32)
    cache = M.init_cache(cfg, pcfg, 2, 32)
    texts = [eng._prefill.lower(params, {"tokens": toks}, {}).as_text(),
             eng._decode.lower(params, toks[:, :1], cache, jnp.int32(16),
                               {}).as_text()]
    for text in texts:
        dots = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
        assert dots
        highest = ["precision = [HIGHEST, HIGHEST]" in ln for ln in dots]
        assert all(highest) if compute_dtype == "float32" else not any(highest)


def test_engine_first_logits_pick_first_tokens():
    """`first_logits` holds the prefill logits the wave's first tokens were
    read from, in request order."""
    cfg = get_config("musicgen-large").reduced()
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    eng = Engine(cfg, PCFG, make_ctx(make_host_mesh()), params, max_len=48)
    assert eng.first_logits is None
    rng = np.random.default_rng(1)
    outs = eng.generate([
        Request(prompt=rng.integers(1, cfg.vocab, size=16).astype(np.int32),
                max_new_tokens=3) for _ in range(3)])
    logits = np.asarray(eng.first_logits)
    assert logits.shape == (3, cfg.vocab)
    np.testing.assert_array_equal(logits.argmax(-1), [o[0] for o in outs])


def test_engine_spamm_telemetry_on_request_out():
    """With SpAMM enabled, every request's `out` metadata carries the wave's
    gating stats (valid_fraction over the gated prefill GEMMs, plan-cache
    deltas) — surfaced through the jitted, scan-over-layers prefill via the
    context's io_callback taps."""
    from repro.configs import SpammConfig

    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    sc = SpammConfig(enable=True, tau=0.05, tile=16, backend="jnp", levels=1)
    eng = Engine(cfg, PCFG, ctx, params, max_len=64, spamm_cfg=sc)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=24).astype(np.int32),
                    max_new_tokens=4) for _ in range(2)]
    outs = eng.generate(reqs)
    for r, o in enumerate(outs):
        meta = reqs[r].out
        np.testing.assert_array_equal(meta["tokens"], o)
        sp = meta["spamm"]
        assert sp["gated_gemms"] > 0
        assert sp["valid_fraction"] is not None
        assert 0.0 < sp["valid_fraction"] <= 1.0
        assert sp["plan_cache_hits"] >= 0 and sp["plan_cache_misses"] >= 0
    # stats are per wave, not cumulative: a second wave reports afresh
    eng.generate(reqs)
    assert reqs[0].out["spamm"]["gated_gemms"] == sp["gated_gemms"]

    # spamm disabled: metadata still present, stats absent
    eng2 = Engine(cfg, PCFG, ctx, params, max_len=64)
    (o2,) = eng2.generate([Request(prompt=reqs[0].prompt, max_new_tokens=3)])
    assert eng2.spamm_ctx is None


def test_engine_eos_frees_early():
    cfg = get_config("musicgen-large").reduced()
    ctx = make_ctx(make_host_mesh())
    params = M.init_params(cfg, PCFG, jax.random.key(0))
    eng = Engine(cfg, PCFG, ctx, params, max_len=64)
    p = np.arange(1, 17, dtype=np.int32)
    (full,) = eng.generate([Request(prompt=p, max_new_tokens=8)])
    eos = int(full[2])
    (cut,) = eng.generate([Request(prompt=p, max_new_tokens=8, eos_id=eos)])
    assert len(cut) == 3 and cut[-1] == eos
