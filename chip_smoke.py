#!/usr/bin/env python3
"""Chip smoke: the SpAMM serving path end to end on a TPU.

    python chip_smoke.py               # one chip: kernel phase, serving phase
    python chip_smoke.py --four-chips  # four chips: pod-sharded serving and
                                       # the row-partitioned product, each
                                       # against the one-device run

One process holds the chip(s) throughout. Without a TPU the script exits
non-zero before printing any result. On success the last line of stdout is
one JSON object: {"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}. Any failed check raises, so the exit code is non-zero.

Kernel phase: the compiled Pallas kernels (tile 128) against the jnp
oracles of `repro.kernels.ref` on the paper's synthesized decay matrix at
n = 4096. The oracles run under HIGHEST matmul precision, so they are f32
references.

Serving phase: musicgen-large at full width (48 layers, d_model 2048,
d_ff 8192) through `Engine`, built as `repro.launch.serve` builds it:
8 requests of 256 random tokens, 16 new tokens each. It runs dense, then
SpAMM at tau = 0 and at tau > 0 (pallas backend, tile 128, frozen plans).
This phase sets no matmul precision of its own: the engine traces its f32
steps at HIGHEST, so the dense reference's dots are f32 like the kernels'.

Four-chip phase: 512 requests of 128 tokens, 4 new tokens each, over a
4-device "rows" mesh: one request group of tile = 128 requests per chip.
The one-device engine serves the same requests in 4 waves of 128, which is
exactly one shard's program. Depth is cut to 8 of musicgen-large's 48
layers; widths stay full. The parameters are replicated on every chip, and
the 128-request KV cache per chip grows with depth (2.2 GB at 8 layers,
13 GB at 48). block_n = 4 keeps the FFN step tables of a 16384-row shard
inside SMEM (1024 (k, j) pairs per row tile at block_n 1).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TILE = 128
SEED = 0
KERNEL_N = 4096          # the synthesized decay matrix of the kernel phase
SERVE_ARCH = "musicgen-large"
FOUR_CHIP_LAYERS = 8     # depth of the four-chip phase (widths stay full)
# tau > 0 of the serving phase: at full width, 100 leaves the prefill
# attention and FFN GEMMs mostly kept, wo of the first layer about 17 %
# kept (a 2-layer full-width probe on the CPU), so partial work-lists run
SERVE_TAU = 100.0

# Tolerances, each relative to max |reference|.
# f32 kernels: the f32 rounding of this product is 4.2e-7 of max |C|
# (float32 against float64, n = 4096); kernel and oracle sum in different
# orders, so allow 50x that. One bf16 pass would be ~4e-3.
TOL_F32 = 2e-5
# bf16 / int8 kernels are compared with the oracle on the SAME rounded or
# dequantized operands: the products are exact in f32 (bf16 x bf16) or in
# int32 (int8 x int8), leaving only f32 accumulation, as above.
TOL_LOWP = 2e-5
# get-norm: f32 sums of 16384 squares in different orders; a sequential
# sum errs by ~sqrt(16384) * 2^-24 = 7.6e-6 (9.2e-6 measured in interpret
# mode on the CPU).
TOL_NORM = 5e-5
# fused int8 get-norm: x / scale rounds a code the other way when it lands
# within an ulp of a .5 boundary (the kernel's division need not be the
# oracle's): ~1 element in 70000, each moving its tile norm by ~1e-5
# (6.1e-5 measured at n = 2048 in interpret mode on the CPU).
TOL_NORM_Q = 5e-4
# serving logits, tau = 0 against dense: 48 layers of f32 GEMMs whose sums
# run in different orders (kernel tiles against XLA's dot), each ~4e-7
# relative as in the kernel phase, carried through the residual stream and
# 48 RMS norms (6.5e-7 measured on a v5e).
TOL_LOGITS = 2e-5
# four chips against one: the same kernels on the same rows, so only XLA's
# shape-dependent fusion of the dense parts can differ.
TOL_SHARDED = 1e-4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str):
    print(("  ok   " if ok else "  FAIL ") + msg, flush=True)
    if not ok:
        fail(msg)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def first_token_check(ref_logits, got_logits, tol: float) -> tuple:
    """Argmax agreement of two logit sets wherever the reference's top-1
    margin exceeds the tolerance (below it, rounding may legitimately
    flip the pick). Returns (agreeing, decidable, total)."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.asarray(got_logits, np.float64)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = (top2[:, 1] - top2[:, 0]) / np.abs(ref).max()
    decidable = margin > 2 * tol
    agree = ref.argmax(-1) == got.argmax(-1)
    return int((agree & decidable).sum()), int(decidable.sum()), len(ref)


def mid_tau(na, nb) -> float:
    """tau in the widest gap between tile-norm products in their middle
    half. The decay matrices' tile norms depend on |i - k| only, so the
    products cluster, with members that differ in the last bits; in the
    widest gap no product sits within rounding of tau, so norms that differ
    in the last bits (kernel against oracle, one device against four) gate
    the same tiles."""
    prods = np.unique((np.asarray(na, np.float64)[:, None, :]
                       * np.asarray(nb, np.float64).T[None]).ravel())
    lo, hi = prods.size // 4, max(3 * prods.size // 4, prods.size // 4 + 2)
    i = lo + int(np.argmax(prods[lo + 1:hi] / prods[lo:hi - 1]))
    return float(np.sqrt(prods[i] * prods[i + 1]))


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def kernel_phase():
    import jax
    import jax.numpy as jnp

    from repro.core import plan as P
    from repro.data.pipeline import synthesized_decay
    from repro.kernels import getnorm, quantize, ref, spamm_mm

    n = KERNEL_N
    print(f"kernel phase: synthesized decay, n={n}, tile {TILE}", flush=True)
    a = jnp.asarray(synthesized_decay(n, seed=SEED))
    b = jnp.asarray(synthesized_decay(n, seed=SEED + 1))
    hi = jax.default_matmul_precision("highest")

    t0 = time.perf_counter()
    na = getnorm.tile_norms(a, TILE)
    nb = getnorm.tile_norms(b, TILE)
    with hi:
        na_ref = ref.tile_norms_ref(a, TILE)
        nb_ref = ref.tile_norms_ref(b, TILE)
    e = rel_err(na, na_ref)
    check(e <= TOL_NORM, f"tile_norms            rel err {e:.3e} <= {TOL_NORM}")

    nq, sq = getnorm.tile_norms_quant(a, TILE)
    q, s_ref = quantize.quantize_tiles(a, TILE)
    with hi:
        nq_ref = ref.tile_norms_ref(quantize.dequantize_tiles(q, s_ref, TILE),
                                    TILE)
    e = rel_err(nq, nq_ref)
    check(e <= TOL_NORM_Q,
          f"tile_norms_quant norms rel err {e:.3e} <= {TOL_NORM_Q}")
    e = rel_err(sq, s_ref)
    check(e <= TOL_NORM, f"tile_norms_quant scale rel err {e:.3e} <= {TOL_NORM}")

    pooled = getnorm.pool_norms(na_ref)
    with hi:
        e = rel_err(pooled, ref.pool_norms_ref(na_ref))
    check(e <= TOL_NORM, f"pool_norms            rel err {e:.3e} <= {TOL_NORM}")

    tau = mid_tau(na_ref, nb_ref)
    p = P.plan(a, b, tau, tile=TILE, backend="pallas")
    mask = p.mask
    vf = float(p.valid_fraction)
    with hi:
        vf_ref = float(jnp.mean(ref.spamm_mask_ref(na_ref, nb_ref, tau)))
    check(vf == vf_ref and 0.0 < vf < 1.0,
          f"plan valid fraction {vf:.4f} == oracle's {vf_ref:.4f}, in (0, 1)")
    w = p.work
    steps = (w.step_i, w.step_j, w.step_k, w.step_flags)

    c = spamm_mm.spamm_mm_worklist(a, b, *steps, tile=TILE, kb=p.kb)
    with hi:
        c_ref = ref.spamm_matmul_ref(a, b, None, TILE, mask=mask)
    e = rel_err(c, c_ref)
    check(e <= TOL_F32, f"spamm_mm_worklist f32  rel err {e:.3e} <= {TOL_F32}")

    a16, b16 = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    c = spamm_mm.spamm_mm_worklist(a16, b16, *steps, tile=TILE, kb=p.kb)
    with hi:
        c_ref = ref.spamm_matmul_ref(a16.astype(jnp.float32),
                                     b16.astype(jnp.float32), None, TILE,
                                     mask=mask)
    e = rel_err(c, c_ref)
    check(e <= TOL_LOWP, f"spamm_mm_worklist bf16 rel err {e:.3e} <= {TOL_LOWP}")

    aq, sa = quantize.quantize_tiles(a, TILE)
    bq, sb = quantize.quantize_tiles(b, TILE)
    # the int8 kernel takes one tile product a step: the same work-list at
    # kb = 1
    gm, gn, gk = mask.shape
    w1, _ = P.compact_from_triples(*np.nonzero(np.asarray(mask)), gm=gm,
                                   gn=gn, gk=gk)
    c = spamm_mm.spamm_mm_worklist_int8(
        aq, bq, sa, sb,
        *(jnp.asarray(t) for t in (w1.step_i, w1.step_j, w1.step_k,
                                   w1.step_flags)), tile=TILE)
    with hi:
        c_ref = ref.spamm_matmul_ref(quantize.dequantize_tiles(aq, sa, TILE),
                                     quantize.dequantize_tiles(bq, sb, TILE),
                                     None, TILE, mask=mask)
    e = rel_err(c, c_ref)
    check(e <= TOL_LOWP, f"spamm_mm_worklist_int8 rel err {e:.3e} <= {TOL_LOWP}")
    print(f"kernel phase: {time.perf_counter() - t0:.1f}s (compile included)",
          flush=True)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_twice(eng, prompts, max_new):
    """Generate twice: the first call compiles, the second is warm. Returns
    (tokens, first_logits, spamm stats, first_s, warm_s)."""
    from repro.serving.engine import Request

    runs = []
    for _ in range(2):
        reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
        t0 = time.perf_counter()
        outs = eng.generate(reqs)
        logits = np.asarray(eng.first_logits)
        runs.append((outs, logits, reqs[0].out["spamm"],
                     time.perf_counter() - t0))
    (o1, l1, _, s1), (o2, l2, sp, s2) = runs
    check(all(np.array_equal(x, y) for x, y in zip(o1, o2))
          and np.array_equal(l1, l2),
          "a second generate repeats the first's tokens and logits")
    check(all(len(o) == max_new for o in o2),
          f"every request returned {max_new} tokens")
    return o2, l2, sp, s1, s2


def serving_phase():
    import jax

    from repro.configs import SpammConfig, get_config
    from repro.kernels import ops as kops
    from repro.launch.serve import init_params, make_engine

    cfg = get_config(SERVE_ARCH)
    nreq, plen, max_new = 8, 256, 16
    max_len = plen + max_new
    print(f"serving phase: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab}; "
          f"{nreq} requests x {plen} tokens, {max_new} new", flush=True)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab, size=plen).astype(np.int32)
               for _ in range(nreq)]
    t0 = time.perf_counter()
    params = init_params(cfg, SEED)
    jax.block_until_ready(params)
    print(f"  params: {time.perf_counter() - t0:.1f}s", flush=True)

    results = {}
    for name, scfg in (
            ("dense", None),
            ("tau=0", SpammConfig(enable=True, tau=0.0, tile=TILE,
                                  backend="pallas")),
            (f"tau={SERVE_TAU:g}", SpammConfig(
                enable=True, tau=SERVE_TAU, tile=TILE, backend="pallas"))):
        if scfg is not None:
            print(f"  {name}: backend {scfg.backend} -> "
                  f"{kops.resolve_backend(scfg.backend)}, tile "
                  f"{scfg.tile}, frozen plans", flush=True)
            check(kops.resolve_backend(scfg.backend) == "pallas",
                  "resolved backend is pallas")
        eng = make_engine(cfg, params, max_len=max_len, spamm_cfg=scfg)
        toks, logits, sp, first, warm = serve_twice(eng, prompts, max_new)
        check(np.isfinite(logits).all(), f"{name}: logits are finite")
        print(f"  {name}: first generate {first:.1f}s (plan freeze + "
              f"compile + run), warm generate {warm:.2f}s", flush=True)
        if sp is not None:
            print(f"  {name}: valid_fraction prefill "
                  f"{sp['valid_fraction']} decode "
                  f"{sp['decode_valid_fraction']}; gated GEMMs "
                  f"{sp['gated_gemms']} prefill, "
                  f"{sp['decode_gated_gemms']} decode", flush=True)
        results[name] = (toks, logits, sp)
        del eng
        gc.collect()

    d_toks, d_logits, _ = results["dense"]
    z_toks, z_logits, z_sp = results["tau=0"]
    check(z_sp["valid_fraction"] == 1.0 and z_sp["decode_valid_fraction"] == 1.0,
          "tau=0 keeps every tile (valid fraction 1.0)")
    e = rel_err(z_logits, d_logits)
    check(e <= TOL_LOGITS,
          f"tau=0 first-token logits vs dense: rel err {e:.3e} <= {TOL_LOGITS}")
    agree, decidable, total = first_token_check(d_logits, z_logits,
                                                TOL_LOGITS)
    check(agree == decidable,
          f"tau=0 first tokens agree with dense: {agree}/{decidable} "
          f"decidable of {total}")
    same = np.mean([np.array_equal(x, y) for x, y in zip(d_toks, z_toks)])
    print(f"  tau=0 vs dense: {same:.3f} of requests with identical "
          f"{max_new}-token outputs (not required: f32 sums differ)",
          flush=True)
    _, _, t_sp = results[f"tau={SERVE_TAU:g}"]
    vf = t_sp["valid_fraction"]
    check(vf is not None and 0.0 < vf < 1.0,
          f"tau={SERVE_TAU:g} gates: prefill valid fraction {vf} in (0, 1)")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip_phase():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.configs import SpammConfig, get_config
    from repro.core import distributed, plan as P
    from repro.data.pipeline import synthesized_decay
    from repro.launch.serve import init_params, make_engine

    devs = jax.devices()
    check(len(devs) == 4, f"4 devices visible (got {len(devs)})")

    # row-partitioned product over a mesh built from jax.devices()
    n = KERNEL_N
    a = jnp.asarray(synthesized_decay(n, seed=SEED))
    b = jnp.asarray(synthesized_decay(n, seed=SEED + 1))
    p1 = P.plan(a, b, 0.0, tile=TILE, backend="pallas")
    tau = mid_tau(p1.norm_a, p1.norm_b)
    mesh = Mesh(np.array(devs), ("data",))
    t0 = time.perf_counter()
    c4, f4 = distributed.spamm_rowpart(a, b, tau, mesh, axis="data",
                                       tile=TILE, backend="pallas")
    c4.block_until_ready()
    shards = c4.addressable_shards
    owners = sorted(s.device.id for s in shards)
    check(len(shards) == 4 and owners == sorted(d.id for d in devs)
          and all(s.data.shape == (n // 4, n) for s in shards),
          f"spamm_rowpart: 4 row shards of {(n // 4, n)} on devices "
          f"{owners}")
    p1 = P.plan(a, b, tau, tile=TILE, backend="pallas")
    c1 = P.execute(p1, a, b)
    e = rel_err(np.asarray(c4), np.asarray(c1))
    check(e <= TOL_F32
          and abs(float(f4) - float(p1.valid_fraction)) <= 1e-6,
          f"spamm_rowpart vs one device: rel err {e:.3e} <= {TOL_F32}, "
          f"valid fraction {float(f4):.4f} == {float(p1.valid_fraction):.4f}"
          f" ({time.perf_counter() - t0:.1f}s)")

    # pod-sharded engine: one request group of TILE requests per chip
    cfg = dataclasses.replace(get_config(SERVE_ARCH),
                              num_layers=FOUR_CHIP_LAYERS)
    nreq, plen, max_new = 4 * TILE, TILE, 4
    max_len = plen + max_new
    print(f"four-chip serving: {cfg.name} at full width, depth cut to "
          f"{cfg.num_layers} layers; {nreq} requests x {plen} tokens, "
          f"{max_new} new", flush=True)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab, size=plen).astype(np.int32)
               for _ in range(nreq)]
    scfg = SpammConfig(enable=True, tau=0.0, tile=TILE, backend="pallas",
                       block_n=4)
    params = init_params(cfg, SEED)
    eng4 = make_engine(cfg, params, max_len=max_len, spamm_cfg=scfg,
                       mesh_devices=4, shard_max_width=1)
    mesh_ids = sorted(d.id for d in eng4._spamm_mesh.devices.flat)
    check(mesh_ids == sorted(d.id for d in devs),
          f"engine mesh spans devices {mesh_ids}")
    toks4, logits4, _, first, warm = serve_twice(eng4, prompts, max_new)
    lay = eng4.shard_layout
    print(f"  sharded: first generate {first:.1f}s (plan freeze + "
          f"compile + run), warm {warm:.2f}s; shard offsets "
          f"{[int(o) for o in lay['offsets']]}, "
          f"{lay['slot_width']} slots each", flush=True)
    check(lay["real"] == [TILE] * 4, "one request group per chip")
    del eng4
    gc.collect()

    eng1 = make_engine(cfg, params, max_len=max_len, spamm_cfg=scfg)
    toks1, logits1 = [], []
    for w in range(4):
        t, l, _, _, _ = serve_twice(eng1, prompts[w * TILE:(w + 1) * TILE],
                                    max_new)
        toks1 += t
        logits1.append(l)
    logits1 = np.concatenate(logits1)
    e = rel_err(logits4, logits1)
    check(e <= TOL_SHARDED,
          f"sharded first-token logits vs one device: rel err {e:.3e} <= "
          f"{TOL_SHARDED}")
    agree, decidable, total = first_token_check(logits1, logits4,
                                                TOL_SHARDED)
    check(agree == decidable,
          f"sharded first tokens agree: {agree}/{decidable} decidable of "
          f"{total}")
    same = np.mean([np.array_equal(x, y) for x, y in zip(toks1, toks4)])
    print(f"  sharded vs one device: {same:.3f} of requests with identical "
          f"{max_new}-token outputs", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device phase (needs 4 chips)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax.devices()[0] is {dev.platform!r} "
             f"({dev.device_kind}); this smoke runs on the chip only")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        serving_phase()
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
