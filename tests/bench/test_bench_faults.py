"""Whole runs of the benchmark's cells on the CPU at a small size, past the
harness's look for a chip: sound, they come out correct; with the timed
path broken underneath, `correct` comes out false."""
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SMALL_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab": 256}
SMALL = {
    "musicgen-large.prefill": {
        "config": {"model": SMALL_MODEL,
                   "spamm": {"tile": 32, "backend": "interpret"}},
        "traffic": {"requests_per_wave": 4, "prompt_len": 32}},
    "decay-8192.r10": {
        "config": {"matrix": {"n": 512, "tile": 32, "backend": "interpret"}}},
}


def run(cell: str, seed: int = 2**31 + 11) -> dict:
    return harness.execute(cell, seed, 0.3, False, t0=time.perf_counter(),
                           require_tpu=False, devices=jax.devices(),
                           overrides=SMALL[cell])


def decode_run(seed: int = 2**31 + 11) -> harness.Run:
    """The serving driver's decode path: the prefill cell at a small size
    with six new tokens per request, driven below the result line so that
    the test reads the waves and the checks themselves."""
    cell = "musicgen-large.prefill"
    spec = harness.cell_spec(harness.load_manifest(), cell)
    for part, over in SMALL[cell].items():
        spec[part] = harness.merged(spec[part], over)
    spec["traffic"]["new_tokens"] = 6
    return harness.measure(spec, seed, 0.3, False, t0=time.perf_counter(),
                           devices=jax.devices(), peaks={})


def break_engine(monkeypatch, fault: str):
    """Make every engine the driver builds run a broken step."""
    from repro.launch import serve

    make = serve.make_engine

    def broken(*args, **kwargs):
        eng = make(*args, **kwargs)
        prefill, decode = eng._prefill, eng._decode
        if fault == "prefill_token":
            def step(*a):
                cache, logits = prefill(*a)
                return cache, logits.at[:, 0].add(100.0)
            eng._prefill = step
        elif fault == "decode_token":
            def step(*a):
                logits, cache = decode(*a)
                return logits.at[:, 0].add(100.0), cache
            eng._decode = step
        elif fault == "decode_state_unchanged":
            def step(params, inp, cache, pos, frozen):
                logits, _ = decode(params, inp, cache, pos, frozen)
                return logits, cache
            eng._decode = step
        return eng

    monkeypatch.setattr(serve, "make_engine", broken)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"]
    assert list(r)[-1] == "checks"


def test_sound_decoding_is_correct():
    r = decode_run()
    assert r.checks and all(c.ok for c in r.checks), r.checks
    assert all(w["out_tokens"] == 4 * 6 for w in r.waves)


def test_broken_prefill_is_not_correct(monkeypatch):
    break_engine(monkeypatch, "prefill_token")
    r = run("musicgen-large.prefill")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["decode_token", "decode_state_unchanged"])
def test_broken_decode_step_is_not_correct(monkeypatch, fault):
    break_engine(monkeypatch, fault)
    r = decode_run()
    assert not all(c.ok for c in r.checks), (fault, r.checks)


@pytest.mark.parametrize("fault", ["answer_altered", "gate_raised"])
def test_broken_product_is_not_correct(monkeypatch, fault):
    from repro.core import spamm as spamm_mod

    orig = spamm_mod.spamm

    def broken(a, b, tau, **kw):
        if fault == "gate_raised":
            return orig(a, b, tau * 1.5, **kw)
        c, info = orig(a, b, tau, **kw)
        return c.at[:32, :32].multiply(0.5), info

    monkeypatch.setattr(spamm_mod, "spamm", broken)
    r = run("decay-8192.r10")
    assert not r["correct"], (fault, r["checks"])


def test_no_accelerator_means_no_result(capsys):
    code = harness.main(["--workload", "decay-8192.r10", "--seed", "1",
                         "--seconds", "1"], t0=time.perf_counter())
    assert code != 0
    assert capsys.readouterr().out == ""
