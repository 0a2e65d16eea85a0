"""Serving cells of a DeepSeek-V3-block MoE configuration: a closed loop
of back-to-back waves through the engine, as `bench.drivers.serve` runs
them, on the configuration's share of the experts.

Set-up: weights on the device from the seed (`bench.deepseek_weights`),
the engine as `repro.launch.serve.make_engine` builds it with
observability on, and one warm-up wave of the cell's own shapes. The window
runs whole waves until the first that ends after `seconds`; each wave
records the expert layers' counters the engine returned
(`Request.out["moe"]`) for the readers.

Correctness, after the window: for a sample of the window's waves drawn
from the seed, the plain reference (`bench.refs.deepseek_v3`) runs each
sequence (prompt, then its served tokens) under the engine's own expert
selections (`Engine.moe_routes`, from the prefill and every decode step).
Three numbers are compared:

* `logits_err`: the widest gap between the wave's prefill logits and the
  reference's, over the wave's widest reference logit;
* `token_mismatch`: served tokens off the reference's top token where its
  top two lie more than twice `logits_err`'s limit apart (of the
  position's widest logit). Exact: limit 0;
* `route_mismatch`: (token, layer) pairs whose selected expert set differs
  from the reference's own top-k where the reference's k-th choice leads
  its (k+1)-th by more than `route_margin` (limits). Exact: limit 0. The
  forced routes keep the comparison of logits and tokens independent of
  near-ties; this check keeps the routes themselves honest.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import deepseek_weights
from bench.drivers.serve import WARMUP, prompts
from bench.harness import Check, memory_peak


def model_config(model: dict):
    """The configuration file's `model` group as a `ModelConfig`."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

    kw = dict(model)
    kw["mla"] = MLAConfig(**model["mla"])
    kw["moe"] = MoEConfig(**model["moe"])
    return ModelConfig(**kw)


def build_engine(model: dict, spamm: dict, params, max_len: int, obs):
    from repro.configs import SpammConfig
    from repro.launch.serve import make_engine

    scfg = SpammConfig(enable=True, tau=spamm["tau"], tile=spamm["tile"],
                       backend=spamm["backend"], block_n=spamm["block_n"])
    return make_engine(model_config(model), params, max_len=max_len,
                       spamm_cfg=scfg, obs=obs)


def wave_routes(routes, batch: int, prompt_len: int):
    """The engine's routes of one wave as (MoE layers, B, P + N - 1, k):
    the prefill's positions, then one per decode step."""
    pre, dec = (np.asarray(r) if r is not None else None for r in routes)
    layers, _, k = pre.shape
    out = pre.reshape(layers, batch, prompt_len, k)
    if dec is not None:                    # (steps, L, B, k)
        out = np.concatenate([out, dec.transpose(1, 2, 0, 3)], axis=2)
    return out


def sequence_checks(params, model: dict, limits: dict, toks, served, first,
                    routes, forward, fed=None) -> dict:
    """The three readings of one wave: `forward(params, seq, routes)`
    gives (logits, own routes, margins) of one sequence, each sequence the
    prompt and then `fed` (default: the served tokens) but its last."""
    p = toks.shape[1]
    fed = served if fed is None else fed
    err = 0.0
    mismatch = flips = 0
    for b in range(toks.shape[0]):
        seq = np.concatenate([toks[b], fed[b, :-1]])
        logits, own, margin = (np.asarray(x) for x in
                               forward(params, seq, routes[:, b]))
        ref = np.asarray(logits, np.float64)[p - 1:]
        err = max(err, float(np.abs(np.asarray(first[b], np.float64)
                                    - ref[0]).max() / np.abs(ref[0]).max()))
        top2 = np.sort(ref, axis=-1)[..., -2:]
        scale = np.abs(ref).max(axis=-1)
        decidable = top2[:, 1] - top2[:, 0] > 2 * limits["logits_err"] * scale
        mismatch += int(((ref.argmax(-1) != served[b]) & decidable).sum())
        differ = np.any(np.sort(own, -1) != np.sort(routes[:, b], -1), -1)
        flips += int((differ & (margin > limits["route_margin"])).sum())
    return {"logits_err": err, "token_mismatch": float(mismatch),
            "route_mismatch": float(flips)}


def served_checks(params, model: dict, limits: dict, sample: list) -> list:
    """The comparisons over `sample`, a list of (prompts (B, P), served
    (B, N), prefill logits (B, V), routes (L, B, P + N - 1, k))."""
    from bench.refs import deepseek_v3

    def forward(params, seq, routes):
        return deepseek_v3.forward(params, seq, model, routes=routes)

    worst = {"logits_err": 0.0, "token_mismatch": 0.0, "route_mismatch": 0.0}
    for toks, served, first, routes in sample:
        got = sequence_checks(params, model, limits, toks, served, first,
                              routes, forward)
        worst["logits_err"] = max(worst["logits_err"], got["logits_err"])
        worst["token_mismatch"] += got["token_mismatch"]
        worst["route_mismatch"] += got["route_mismatch"]
    return [Check(name, value, limits[name]) for name, value in worst.items()]


def run(run, window, *, t0: float) -> None:
    from repro.obs import Observability
    from repro.serving.engine import Request

    model, traffic = run.config["model"], run.traffic
    model_config(model)        # a program without the configuration's
    batch, plen = traffic["requests_per_wave"], traffic["prompt_len"]
    new = traffic["new_tokens"]  # parts fails here, before any weight
    params = deepseek_weights.deepseek_v3(model, run.seed)
    obs = Observability(process_name="bench")
    eng = build_engine(model, run.config["spamm"], params, plen + new, obs)

    def wave(i):
        toks = prompts(run.seed, i, batch, plen, model["vocab"])
        reqs = [Request(prompt=t, max_new_tokens=new) for t in toks]
        return reqs, eng.generate(reqs)

    wave(WARMUP)
    kept = []            # (wave index, served, logits, routes), on device
    n_spans = len(obs.tracer.events)
    with window.open() as w:
        run.setup_s = w.t0 - t0
        i = 0
        while True:
            submit = w.elapsed
            reqs, outs = wave(i)
            done = w.elapsed
            served = np.stack(outs)
            kept.append((i, served, eng.first_logits, eng.moe_routes))
            run.waves.append({"submit_s": submit, "done_s": done,
                              "batch": batch, "prompt_len": plen,
                              "new_tokens": new,
                              "out_tokens": int(served.size),
                              "moe": reqs[0].out.get("moe")})
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
               kept[j][1], np.asarray(kept[j][2]),
               wave_routes(kept[j][3], batch, plen)) for j in pick]
    del eng, kept, obs
    gc.collect()
    t_ref = time.perf_counter()
    run.checks = served_checks(params, model, run.config["limits"], sample)
    run.reference_s = time.perf_counter() - t_ref
