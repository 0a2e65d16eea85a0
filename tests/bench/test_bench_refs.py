"""The benchmark's plain references against the system under test at a
small size on the CPU (Pallas kernels in interpret mode), and their
controls one precision step below f32 failing the configurations' limits.
"""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import weights  # noqa: E402
from bench.refs import gated_product as gp  # noqa: E402
from bench.refs import transformer  # noqa: E402

SMALL = {"name": "musicgen-large", "family": "audio", "num_layers": 2,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
         "d_ff": 128, "vocab": 256, "act": "gelu_mlp", "rope_theta": 10000.0,
         "norm_eps": 1e-05, "frontend": "audio_stub"}
SERVE_LIMITS = json.loads(
    (ROOT / "bench/configs/musicgen-large.json").read_text())["limits"]
PRODUCT_LIMITS = json.loads(
    (ROOT / "bench/configs/decay-8192.json").read_text())["limits"]


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def served():
    """Weights, prompts, and the engine's first logits and 4 greedy tokens
    (tau 0, interpret backend, tile 32, frozen plans)."""
    from repro.configs import SpammConfig
    from repro.configs.base import ModelConfig
    from repro.launch.serve import make_engine
    from repro.serving.engine import Request

    params = weights.transformer(SMALL, 3)
    toks = np.random.default_rng(0).integers(1, 256, size=(4, 32))
    eng = make_engine(ModelConfig(**SMALL), params, max_len=36,
                      spamm_cfg=SpammConfig(enable=True, tau=0.0, tile=32,
                                            backend="interpret"))
    outs = eng.generate([Request(prompt=t.astype(np.int32),
                                 max_new_tokens=4) for t in toks])
    return params, toks, np.asarray(eng.first_logits), np.stack(outs)


def test_weights_have_the_engines_layout():
    from repro.configs import ParallelConfig
    from repro.configs.base import ModelConfig
    from repro.models import model as M

    ours = jax.eval_shape(lambda: weights.transformer(SMALL, 0))
    theirs = jax.eval_shape(lambda: M.init_params(
        ModelConfig(**SMALL), ParallelConfig(), jax.random.key(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape
                                        and a.dtype == b.dtype, ours, theirs))


def test_weights_come_from_the_seed():
    a = weights.transformer(SMALL, 2**31 + 5)
    b = weights.transformer(SMALL, 2**31 + 5)
    c = weights.transformer(SMALL, 5)
    assert np.array_equal(a["unembed"]["kernel"], b["unembed"]["kernel"])
    assert not np.array_equal(a["unembed"]["kernel"], c["unembed"]["kernel"])


def test_reference_agrees_with_the_engine(served):
    params, toks, first, outs = served
    seq = np.concatenate([toks, outs[:, :-1]], axis=1)
    ref = np.asarray(transformer.logits(params, seq, SMALL))[:, 31:]
    assert rel(first, ref[:, 0]) <= SERVE_LIMITS["logits_err"]
    assert np.array_equal(ref.argmax(-1), outs)


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_serving_control_fails_the_logits_limit(served, precision):
    params, toks, _, outs = served
    seq = np.concatenate([toks, outs[:, :-1]], axis=1)
    ref = np.asarray(transformer.logits(params, seq, SMALL))[:, 31]
    low = np.asarray(transformer.logits(params, seq, SMALL, precision))[:, 31]
    assert rel(low, ref) > SERVE_LIMITS["logits_err"]


@pytest.fixture(scope="module")
def product():
    from repro.core.spamm import spamm

    a, b = weights.decay_matrices(512, 2, 9, c=0.1, lam=0.1)
    na, nb = np.asarray(gp.tile_norms(a, 32)), np.asarray(gp.tile_norms(b, 32))
    tau = gp.choose_tau(na, nb, 0.1)
    mask = gp.gate(na, nb, tau)
    c, info = spamm(a, b, tau, tile=32, backend="interpret")
    return a, b, mask, c, info


def test_tau_sits_in_a_gap_near_the_ratio(product):
    a, b, mask, _, _ = product
    na, nb = np.asarray(gp.tile_norms(a, 32)), np.asarray(gp.tile_norms(b, 32))
    tau = gp.choose_tau(na, nb, 0.1)
    prods = na[:, None, :].astype(np.float64) * nb.T[None]
    assert 0.05 < mask.mean() < 0.2
    near = np.abs(prods / tau - 1.0)
    assert near.min() >= gp.GAP / 2


def test_gated_reference_agrees_with_spamm(product):
    a, b, mask, c, info = product
    ref = gp.product(a, b, jax.numpy.asarray(mask), 32)
    assert round(float(info.valid_fraction) * mask.size) == int(mask.sum())
    assert rel(c, ref) <= PRODUCT_LIMITS["product_err"]
    dense = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert rel(ref, dense) > 1e-3       # the gate drops real work


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_product_control_fails_the_limit(product, precision):
    a, b, mask, _, _ = product
    m = jax.numpy.asarray(mask)
    ref = gp.product(a, b, m, 32)
    assert rel(gp.product(a, b, m, 32, precision), ref) \
        > PRODUCT_LIMITS["product_err"]
