#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip, at a
cell's own size: the program's numbers and the control's, seed by seed.

    python bench/control.py --workload <cell> --seeds 1,2,3

The control is the plain reference put in the program's place and computed
one precision step below the configuration's f32 at HIGHEST: "high", three
bf16 passes (`bench.refs.arith`). For each seed it drives the cell's timed
path for one wave or one product (the cell's own shapes and load), then
prints one JSON line per seed with the program's and the control's numbers
under the names the run compares. The benchmark's own runs never run this.
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

from bench import harness, weights  # noqa: E402

CONTROL = "high"


def serve_readings(spec, seed, precision):
    from bench.drivers import serve
    from bench.refs import transformer
    from repro.obs import Observability
    from repro.serving.engine import Request

    model, traffic = spec["config"]["model"], spec["traffic"]
    limits = spec["config"]["limits"]
    batch, plen = traffic["requests_per_wave"], traffic["prompt_len"]
    new = traffic["new_tokens"]
    params = weights.transformer(model, seed)
    eng = serve.build_engine(model, spec["config"]["spamm"], params,
                             plen + new, Observability(process_name="bench"))
    toks = serve.prompts(seed, 0, batch, plen, model["vocab"])
    outs = np.stack(eng.generate([Request(prompt=t, max_new_tokens=new)
                                  for t in toks]))
    first = np.asarray(eng.first_logits)
    del eng
    gc.collect()
    program = serve.served_checks(params, model, limits,
                                  [(toks, outs, first)])
    # the control in the program's place: its logits at every served
    # position of the same prompts and tokens, its top token served
    seq = np.concatenate([toks, outs[:, :-1]], axis=1)
    low = np.asarray(transformer.logits(params, seq, model, precision))
    low = low[:, plen - 1:]
    control = serve.served_checks(params, model, limits,
                                  [(toks, low.argmax(-1), low[:, 0])])
    return program, control


def product_readings(spec, seed, precision):
    from bench.drivers import product
    from bench.refs import gated_product as ref

    mat, traffic = spec["config"]["matrix"], spec["traffic"]
    tile = mat["tile"]
    a, b = weights.decay_matrices(mat["n"], 2, seed, c=mat["c"],
                                  lam=mat["lam"])
    na = np.asarray(ref.tile_norms(a, tile))
    nb = np.asarray(ref.tile_norms(b, tile))
    tau = ref.choose_tau(na, nb, traffic["valid_ratio"])
    mask = ref.gate(na, nb, tau)
    call = product.spamm_call(mat, tau)
    c, frac = call(a, b)
    got = np.asarray(c)
    want = np.asarray(ref.product(a, b, mask, tile))
    low = np.asarray(ref.product(a, b, mask, tile, precision))
    scale = np.abs(want).max()
    lim = spec["config"]["limits"]
    gate = abs(round(float(frac) * mask.size) - int(mask.sum()))
    program = [harness.Check("gate_diff", float(gate), lim["gate_diff"]),
               harness.Check("product_err",
                             float(np.abs(got - want).max() / scale),
                             lim["product_err"])]
    control = [harness.Check("gate_diff", 0.0, lim["gate_diff"]),
               harness.Check("product_err",
                             float(np.abs(low - want).max() / scale),
                             lim["product_err"])]
    return program, control


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    spec = harness.cell_spec(harness.load_manifest(), args.workload)
    harness.accelerator(spec["cell"]["chips"])
    harness.enable_compile_cache()
    readings = {"serve": serve_readings, "product": product_readings}[
        spec["config"]["driver"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        program, control = readings(spec, seed, CONTROL)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_precision": CONTROL,
            "program": {c.name: c.value for c in program},
            "control": {c.name: c.value for c in control},
            "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
