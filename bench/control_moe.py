#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip, for a
cell of the MoE serving driver (`bench.drivers.serve_moe`), which
`bench/control.py` does not dispatch to: the program's numbers and the
control's, seed by seed.

    python bench/control_moe.py --workload moonlight-16b-a3b.chat --seeds 1,2,3

For each seed one wave of the cell's own shapes runs through the engine,
and the program's three numbers are read as the cell's run reads them.
The control is the plain reference put in the program's place one
precision step below f32 at HIGHEST ("high", three bf16 passes;
`bench.refs.arith`): fed the program's sequences, it chooses its own
routes, and its prefill logits, its top tokens and its routes are compared
with the reference at HIGHEST under the control's routes, by the same
three numbers. One JSON line per seed. The benchmark's own runs never run
this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import deepseek_weights, harness  # noqa: E402

CONTROL = "high"


def readings(spec, seed, precision):
    """(program readings, control readings) of one wave, each a dict of
    the three numbers."""
    from bench.drivers import serve_moe
    from bench.drivers.serve import prompts
    from bench.refs import deepseek_v3
    from repro.obs import Observability
    from repro.serving.engine import Request

    model, traffic = spec["config"]["model"], spec["traffic"]
    limits = spec["config"]["limits"]
    batch, plen = traffic["requests_per_wave"], traffic["prompt_len"]
    new = traffic["new_tokens"]
    params = deepseek_weights.deepseek_v3(model, seed)
    eng = serve_moe.build_engine(model, spec["config"]["spamm"], params,
                                 plen + new,
                                 Observability(process_name="bench"))
    toks = prompts(seed, 0, batch, plen, model["vocab"])
    outs = np.stack(eng.generate([Request(prompt=t, max_new_tokens=new)
                                  for t in toks]))
    first = np.asarray(eng.first_logits)
    routes = serve_moe.wave_routes(eng.moe_routes, batch, plen)
    del eng
    gc.collect()

    def highest(params, seq, r):
        return deepseek_v3.forward(params, seq, model, routes=r)

    program = serve_moe.sequence_checks(params, model, limits, toks, outs,
                                        first, routes, highest)
    # the control in the program's place, fed the program's sequences
    low_logits, low_tokens, low_routes = [], [], []
    for b in range(batch):
        seq = np.concatenate([toks[b], outs[b, :-1]])
        lg, own, _ = (np.asarray(x) for x in deepseek_v3.forward(
            params, seq, model, precision=precision))
        low_logits.append(lg[plen - 1])
        low_tokens.append(lg[plen - 1:].argmax(-1))
        low_routes.append(own)
    control = serve_moe.sequence_checks(
        params, model, limits, toks, np.stack(low_tokens),
        np.stack(low_logits), np.stack(low_routes, axis=1), highest,
        fed=outs)
    return program, control


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    spec = harness.cell_spec(harness.load_manifest(), args.workload)
    harness.accelerator(spec["cell"]["chips"])
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        program, control = readings(spec, seed, CONTROL)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_precision": CONTROL, "program": program,
            "control": control,
            "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
