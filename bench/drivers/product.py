"""Product cells: back-to-back norm-gated products of decay matrices.

Set-up makes a pool of `pool` operand pairs on the device from the seed
(`bench.weights.decay_matrices`) and sets tau at the traffic's valid ratio,
in a gap between tile-norm products (`bench.refs.gated_product`). The
magnitudes, and so the norms, the gate and the work, are the same for every
seed and every pair; only the signs differ. One warm-up product compiles
what the window runs. The window cycles through the pool, each product
ending in `block_until_ready`, until the first product that ends after
`seconds`.

Each product is `repro.core.spamm.spamm(a, b, tau, tile=, backend=)` on
one chip. A product cell with another entry point (the row-partitioned
product over four chips) is a driver file of its own.

Correctness, after the window: two products drawn from the seed (one of the
first pool cycle, and the last) are kept and compared with the plain gated
product at HIGHEST:

* `gate_diff`: surviving triples the program reports against the
  reference's count. Exact: limit 0;
* `product_err`: the widest gap between the program's C and the
  reference's, over the reference's widest entry.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import weights
from bench.harness import Check, memory_peak
from bench.refs import gated_product as ref


def spamm_call(mat: dict, tau: float):
    """fn(a, b) -> (C, valid fraction): the program's gated product."""
    from repro.core.spamm import spamm

    def call(a, b):
        c, info = spamm(a, b, tau, tile=mat["tile"], backend=mat["backend"])
        return c, info.valid_fraction
    return call


def run(run, window, *, t0: float) -> None:
    import jax

    mat, traffic = run.config["matrix"], run.traffic
    n, tile, pool = mat["n"], mat["tile"], traffic["pool"]
    mats = weights.decay_matrices(n, 2 * pool, run.seed, c=mat["c"],
                                  lam=mat["lam"])
    pairs = [(mats[2 * p], mats[2 * p + 1]) for p in range(pool)]
    na = np.asarray(ref.tile_norms(pairs[0][0], tile))
    nb = np.asarray(ref.tile_norms(pairs[0][1], tile))
    tau = ref.choose_tau(na, nb, traffic["valid_ratio"])
    mask = ref.gate(na, nb, tau)
    ii, jj, kk = np.nonzero(mask)
    run.product_work = {"n": n, "tile": tile, "triples": (ii, jj, kk)}
    call = spamm_call(mat, tau)
    jax.block_until_ready(call(*pairs[0]))

    rng = np.random.default_rng([run.seed % 2**63, 7])
    first = int(rng.integers(pool))
    kept = {}
    with window.open() as w:
        run.setup_s = w.t0 - t0
        i = 0
        while True:
            c, frac = call(*pairs[i % pool])
            c = jax.block_until_ready(c)
            if i == first:
                kept[i] = (c, frac)
            last = (i, c, frac)
            i += 1
            if w.elapsed >= run.seconds:
                break
    run.window_s = w.t1 - w.t0
    run.products = run.attempted = i
    kept[last[0]] = last[1:]
    run.memory_peak_bytes = memory_peak(run.devices)
    del last, c
    gc.collect()

    t_ref = time.perf_counter()
    total = mask.size
    want = int(mask.sum())
    gate_diff = prod_err = 0.0
    for j, (c, frac) in sorted(kept.items()):
        c_ref = np.asarray(ref.product(*pairs[j % pool], mask, tile))
        got = np.asarray(c)
        prod_err = max(prod_err, float(np.abs(got - c_ref).max()
                                       / np.abs(c_ref).max()))
        gate_diff = max(gate_diff, abs(round(float(frac) * total) - want))
        del c_ref, got
    lim = run.config["limits"]
    run.checks = [Check("gate_diff", float(gate_diff), lim["gate_diff"]),
                  Check("product_err", prod_err, lim["product_err"])]
    run.reference_s = time.perf_counter() - t_ref
