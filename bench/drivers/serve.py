"""Serving cells: a closed loop of back-to-back waves through the engine.

A wave is one `Engine.generate` call with `requests_per_wave` prompts of
`prompt_len` random tokens and `new_tokens` greedy tokens each, no EOS.
Prompts come from (seed, wave index); every seed gets the same sizes.

Set-up: weights on the device from the seed (`bench.weights`), the engine
as `repro.launch.serve.make_engine` builds it with observability on, and
one warm-up wave of the cell's own shapes (freezing the plans and
compiling every program the window runs). The window runs whole waves
from its start until the first wave that ends after `seconds`.

Correctness, after the window: for a sample of the window's waves drawn
from the seed, the plain reference (`bench.refs.transformer`) runs over
each prompt with its served tokens. Two numbers are compared:

* `logits_err`: the widest gap between the wave's prefill logits
  (`Engine.first_logits`) and the reference's, over the wave's widest
  reference logit;
* `token_mismatch`: served tokens that are not the reference's top token
  at a position where the reference's top two logits lie further apart
  than twice `logits_err`'s limit (of that position's widest logit), so
  that no rounding inside the limit can explain the difference. Exact:
  its limit is 0.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import weights
from bench.harness import Check, memory_peak

WARMUP = -1


def prompts(seed: int, wave: int, batch: int, length: int, vocab: int):
    rng = np.random.default_rng([seed % 2**63, wave + 1])
    return rng.integers(1, vocab, size=(batch, length)).astype(np.int32)


def build_engine(model: dict, spamm: dict, params, max_len: int, obs):
    from repro.configs import SpammConfig
    from repro.configs.base import ModelConfig
    from repro.launch.serve import make_engine

    scfg = SpammConfig(enable=True, tau=spamm["tau"], tile=spamm["tile"],
                       backend=spamm["backend"], block_n=spamm["block_n"])
    return make_engine(ModelConfig(**model), params, max_len=max_len,
                       spamm_cfg=scfg, obs=obs)


def served_checks(params, model: dict, limits: dict, sample: list) -> list:
    """The two comparisons over `sample`, a list of (prompts (B, P),
    served tokens (B, N), prefill logits (B, V)) of the sampled waves."""
    from bench.refs import transformer

    logits_err = 0.0
    mismatch = 0
    for toks, served, first in sample:
        p = toks.shape[1]
        seq = np.concatenate([toks, served[:, :-1]], axis=1)
        ref = np.asarray(transformer.logits(params, seq, model), np.float64)
        ref = ref[:, p - 1:]                              # (B, N, V)
        first = np.asarray(first, np.float64)
        logits_err = max(logits_err, float(
            np.abs(first - ref[:, 0]).max() / np.abs(ref[:, 0]).max()))
        top2 = np.sort(ref, axis=-1)[..., -2:]
        scale = np.abs(ref).max(axis=-1)
        decidable = top2[..., 1] - top2[..., 0] > 2 * limits["logits_err"] * scale
        wrong = ref.argmax(-1) != served
        mismatch += int((wrong & decidable).sum())
    return [Check("logits_err", logits_err, limits["logits_err"]),
            Check("token_mismatch", float(mismatch),
                  limits["token_mismatch"])]


def run(run, window, *, t0: float) -> None:
    from repro.obs import Observability
    from repro.serving.engine import Request

    model, traffic = run.config["model"], run.traffic
    batch, plen = traffic["requests_per_wave"], traffic["prompt_len"]
    new = traffic["new_tokens"]
    params = weights.transformer(model, run.seed)
    obs = Observability(process_name="bench")
    eng = build_engine(model, run.config["spamm"], params, plen + new, obs)

    def wave(i):
        toks = prompts(run.seed, i, batch, plen, model["vocab"])
        reqs = [Request(prompt=t, max_new_tokens=new) for t in toks]
        return toks, eng.generate(reqs)

    wave(WARMUP)
    kept = []                       # (wave index, served (B, N), logits)
    n_spans = len(obs.tracer.events)
    with window.open() as w:
        run.setup_s = w.t0 - t0
        i = 0
        while True:
            submit = w.elapsed
            _, outs = wave(i)
            done = w.elapsed
            served = np.stack(outs)
            kept.append((i, served, eng.first_logits))
            run.waves.append({"submit_s": submit, "done_s": done,
                              "batch": batch, "prompt_len": plen,
                              "new_tokens": new,
                              "out_tokens": int(served.size)})
            run.attempted += batch
            run.failed += sum(len(o) != new for o in outs)
            i += 1
            if done >= run.seconds:
                break
    run.window_s = w.t1 - w.t0
    run.spans = list(obs.tracer.events[n_spans:])
    run.memory_peak_bytes = memory_peak(run.devices)

    rng = np.random.default_rng([run.seed % 2**63, 7])
    pick = sorted(rng.choice(len(kept), min(traffic["check_waves"], len(kept)),
                             replace=False))
    sample = [(prompts(run.seed, kept[j][0], batch, plen, model["vocab"]),
               kept[j][1], np.asarray(kept[j][2])) for j in pick]
    del eng, kept, obs
    gc.collect()
    t_ref = time.perf_counter()
    run.checks = served_checks(params, model, run.config["limits"], sample)
    run.reference_s = time.perf_counter() - t_ref
