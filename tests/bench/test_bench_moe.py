"""The DeepSeek-V3-block serving cell at a small Moonlight-shaped size on
the CPU: the engine against the plain reference (`bench.refs.deepseek_v3`),
the reference against the installed `transformers` implementation, the
cell's checks failing for planted faults and for the `high` control."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import deepseek_weights, harness  # noqa: E402
from bench.drivers import serve_moe  # noqa: E402
from bench.refs import deepseek_v3  # noqa: E402

CELL = "moonlight-16b-a3b.chat"
SMALL = {"name": "moonlight-small", "family": "moe", "num_layers": 3,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 24,
         "d_ff": 128, "vocab": 256, "act": "silu", "rope_theta": 50000.0,
         "norm_eps": 1e-05, "first_dense": 1,
         "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 8, "v_head_dim": 16},
         "moe": {"num_experts": 16, "top_k": 3, "expert_ff": 32,
                 "num_shared": 2, "shared_ff": 64, "scoring": "sigmoid",
                 "score_bias": True,
                 "routed_scale": 2.446, "shared_gate": False, "held": 8}}
SPAMM = {"tau": 0.0, "tile": 16, "backend": "interpret", "block_n": 1}
LIMITS = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                    .read_text())["limits"]
# the engine against the reference at this size, f32 on the CPU on both
# sides, differing in summation order only: 0.9e-6 to 1.3e-6 of the widest
# logit on three seeds. Tighter than the cell's limit, which readings at
# the cell's own size on the chip set (PERF.md section 4).
LOGITS_TOL = 4e-6
OVERRIDES = {"config": {"model": SMALL, "spamm": SPAMM},
             "traffic": {"requests_per_wave": 2, "prompt_len": 32,
                         "new_tokens": 4, "check_waves": 1}}
B, P, N = 3, 32, 5


@pytest.fixture(scope="module")
def served():
    """Weights, prompts, and a wave's served tokens, prefill logits and
    expert routes through the engine (tau 0, interpret backend, tile 16,
    frozen plans, the expert work-list kernel)."""
    from repro.obs import Observability
    from repro.serving.engine import Request

    params = deepseek_weights.deepseek_v3(SMALL, 5)
    toks = np.random.default_rng(0).integers(1, 256, size=(B, P)).astype(
        np.int32)
    eng = serve_moe.build_engine(SMALL, SPAMM, params, P + N,
                                 Observability(process_name="test"))
    reqs = [Request(prompt=t, max_new_tokens=N) for t in toks]
    outs = np.stack(eng.generate(reqs))
    routes = serve_moe.wave_routes(eng.moe_routes, B, P)
    return params, toks, outs, np.asarray(eng.first_logits), routes, reqs


def highest(params, seq, routes):
    return deepseek_v3.forward(params, seq, SMALL, routes=routes)


def test_weights_have_the_engines_layout():
    from repro.configs import ParallelConfig
    from repro.models import model as M

    ours = jax.eval_shape(lambda: deepseek_weights.deepseek_v3(SMALL, 0))
    theirs = jax.eval_shape(lambda: M.init_params(
        serve_moe.model_config(SMALL),
        ParallelConfig(param_dtype="float32"), jax.random.key(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_engine_agrees_with_the_reference_under_its_routes(served):
    """Prefill, then decoding through the latent cache, against the
    reference's full forward pass under the engine's selections, within
    `LOGITS_TOL` of the widest logit. Routes: the
    engine's and the reference's own top-3 agree wherever the reference's
    3rd choice leads its 4th by more than the cell's margin."""
    params, toks, outs, first, routes, _ = served
    got = serve_moe.sequence_checks(params, SMALL, LIMITS, toks, outs, first,
                                    routes, highest)
    assert got["logits_err"] <= LOGITS_TOL, got
    assert got["token_mismatch"] == 0 and got["route_mismatch"] == 0, got
    assert routes.shape == (2, B, P + N - 1, 3)


def test_every_step_logits_agree_through_the_cache(served):
    """Each decode step's logits, not only its argmax: re-run the engine's
    steps by hand against the reference's logits at every position."""
    from repro.configs import SpammConfig
    from repro.launch.serve import make_engine
    from repro.serving.engine import Request

    params, toks, outs, _, routes, _ = served
    eng = make_engine(serve_moe.model_config(SMALL), params, max_len=P + N,
                      spamm_cfg=SpammConfig(enable=True, tau=0.0, tile=16,
                                            backend="interpret"))
    logits = []
    dec = eng._decode

    def keep(*a):
        lg, cache = dec(*a)
        logits.append(np.asarray(lg))
        return lg, cache

    eng._decode = keep
    eng.generate([Request(prompt=t, max_new_tokens=N) for t in toks])
    for b in range(B):
        seq = np.concatenate([toks[b], outs[b, :-1]])
        ref = np.asarray(highest(params, seq, routes[:, b])[0])
        for j, lg in enumerate(logits):
            want = ref[P + j]
            err = np.abs(lg[b] - want).max() / np.abs(want).max()
            assert err <= LOGITS_TOL, (b, j, err)


def test_counters_ride_the_wave(served):
    *_, routes, reqs = served
    moe = reqs[0].out["moe"]
    pre = routes[:, :, :P]
    held = 8
    assert moe["tile"] == 16
    assert moe["prefill"]["routed_rows"] == int(np.sum(pre < held))
    assert moe["decode"]["routed_rows"] == int(np.sum(routes[:, :, P:] < held))
    assert moe["prefill"]["dropped_rows"] == moe["decode"]["dropped_rows"] == 0


def _hf_model(params):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    m, mo = SMALL["mla"], SMALL["moe"]
    cfg = tf.DeepseekV3Config(
        vocab_size=SMALL["vocab"], hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, n_shared_experts=2, n_routed_experts=16,
        routed_scaling_factor=2.446, kv_lora_rank=32, q_lora_rank=None,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=1,
        topk_group=1, num_experts_per_tok=mo["top_k"], first_k_dense_replace=1,
        norm_topk_prob=True, max_position_embeddings=128, rope_theta=50000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False, attention_bias=False)
    cfg._attn_implementation = "eager"
    model = tf.DeepseekV3ForCausalLM(cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    sd = {"model.embed_tokens.weight": t(params["embed"]["embedding"]),
          "model.norm.weight": t(1 + params["final_norm"]),
          "lm_head.weight": t(params["unembed"]["kernel"].T)}
    layers = [("dense", 0)] + [("layers", i) for i in range(2)]
    for li, (group, i) in enumerate(layers):
        p = jax.tree.map(lambda a: a[i], params[group])
        pre = f"model.layers.{li}."
        sd[pre + "input_layernorm.weight"] = t(1 + p["ln1"])
        sd[pre + "post_attention_layernorm.weight"] = t(1 + p["ln2"])
        a = pre + "self_attn."
        sd[a + "q_proj.weight"] = t(p["mix"]["wq"].T)
        sd[a + "kv_a_proj_with_mqa.weight"] = t(p["mix"]["wkv_a"].T)
        sd[a + "kv_a_layernorm.weight"] = t(1 + p["mix"]["kv_ln"])
        sd[a + "kv_b_proj.weight"] = t(p["mix"]["wkv_b"].T)
        sd[a + "o_proj.weight"] = t(p["mix"]["wo"].T)
        f = pre + "mlp."
        if group == "dense":
            for n, w in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                sd[f + f"{n}_proj.weight"] = t(p["mlp"][w].T)
            continue
        sd[f + "gate.weight"] = t(p["moe"]["router"].T)
        sd[f + "gate.e_score_correction_bias"] = t(p["moe"]["score_bias"])
        for n, w in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
            sd[f + f"shared_experts.{n}_proj.weight"] = t(
                p["moe"]["shared"][w].T)
            for e in range(16):
                sd[f + f"experts.{e}.{n}_proj.weight"] = t(p["moe"][w][e].T)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and all("rotary" in k for k in missing), missing
    return torch, model


def test_reference_follows_the_published_equations():
    """The reference against `transformers`' DeepseekV3ForCausalLM at a
    tiny size, the same weights copied in and all 16 experts held: the
    router's bias-for-selection, MLA with kv_a_layernorm, interleaved rope
    and qk_head_dim ** -0.5, the dense layer and the shared experts. Both
    in f32 on the CPU: 1e-4 of the widest logit allows torch's and XLA's
    summation orders."""
    full = dict(SMALL, moe=dict(SMALL["moe"], held=0))
    params = deepseek_weights.deepseek_v3(full, 9)
    torch, model = _hf_model(params)
    toks = np.random.default_rng(1).integers(1, 256, size=40)
    with torch.no_grad():
        want = model(torch.tensor(toks[None])).logits[0].numpy()
    got = np.asarray(deepseek_v3.forward(params, toks, full)[0])
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4


def run_cell(seed: int = 2**33 + 21) -> dict:
    return harness.execute(CELL, seed, 0.3, False, t0=time.perf_counter(),
                           require_tpu=False, devices=jax.devices()[:1],
                           overrides=OVERRIDES)


def test_sound_run_is_correct():
    r = run_cell()
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"logits_err", "token_mismatch",
                                "route_mismatch"}


def _fault(monkeypatch, fault: str):
    from repro.core import module as spmod
    from repro.models import moe

    if fault == "dropped_row":
        layout = moe._layout

        def dropped(*a, **k):
            row_tok, *rest = layout(*a, **k)
            first = jnp.argmax(row_tok >= 0)
            return (row_tok.at[first].set(-1), *rest)
        monkeypatch.setattr(moe, "_layout", dropped)
    elif fault == "routed_scale":
        route = moe.route

        def scaled(*a, **k):
            ids, w, margin = route(*a, **k)
            return ids, w * (2.0 / 2.446), margin
        monkeypatch.setattr(moe, "route", scaled)
    elif fault == "bf16_experts":
        gemm = spmod.spamm_experts_frozen

        def bf16(x, w, *a):
            y, *rest = gemm(x.astype(jnp.bfloat16).astype(jnp.float32),
                            w.astype(jnp.bfloat16).astype(jnp.float32), *a)
            return (y, *rest)
        monkeypatch.setattr(moe, "spamm_experts_frozen", bf16)
    elif fault == "flipped_route":
        route = moe.route

        def flipped(p, x, cfg):
            ids, w, margin = route(p, x, cfg)
            # token 0's last choice swapped for the expert ranked last
            worst = jnp.argmin(x[:1].astype(jnp.float32) @ p["router"]
                               + p["score_bias"], axis=-1)
            return ids.at[0, -1].set(worst[0].astype(jnp.int32)), w, margin
        monkeypatch.setattr(moe, "route", flipped)


@pytest.mark.parametrize("fault", ["dropped_row", "routed_scale",
                                   "bf16_experts", "flipped_route"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _fault(monkeypatch, fault)
    r = run_cell()
    assert not r["correct"], (fault, r["checks"])
    if fault == "flipped_route":
        assert r["checks"]["route_mismatch"]["value"] >= 1


def test_high_control_is_not_correct():
    """The reference one precision step below f32 at HIGHEST in the
    program's place fails at least one of the three limits at this size
    too."""
    from bench import control_moe

    spec = harness.cell_spec(harness.load_manifest(), CELL)
    for part, over in OVERRIDES.items():
        spec[part] = harness.merged(spec[part], over)
    spec["traffic"]["requests_per_wave"] = 4
    program, control = control_moe.readings(spec, 2**33 + 23, "high")
    assert all(program[k] <= LIMITS[k] for k in program), program
    assert any(control[k] > LIMITS[k] for k in control), control
