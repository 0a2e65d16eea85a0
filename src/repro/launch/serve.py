"""Serving driver: load/init a model, serve batched greedy generation.

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large --reduced \
      --num-requests 8 --prompt-len 32 --max-new 16

`make_engine` is the construction every serving entry point shares (this
CLI and `chip_smoke.py`).
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np

from repro.configs import ParallelConfig, SpammConfig, get_config
from repro.kernels import ops as kops
from repro.kernels.common import LANE
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_ctx, make_host_mesh
from repro.models import model as M
from repro.serving.engine import Engine, Request

# f32 weights and activations; flash attention in 64-token chunks
SERVE_PCFG = ParallelConfig(
    compute_dtype="float32", remat="none", decode_seq_shard=False,
    attn_q_chunk=64, attn_kv_chunk=64,
)


def default_tile(backend: str) -> int:
    """SpAMM tile when none is given: 128 where the kernels are compiled
    (their (tile, tile) blocks must span the 128-wide lane dimension), 32
    on the interpret/jnp backends."""
    return LANE if kops.get_backend(backend).compiled else 32


def init_params(cfg, seed: int):
    """Random weights from `seed`, made in one compiled program so no eager
    per-leaf temporaries sit beside the full-size weights."""
    return jax.jit(functools.partial(M.init_params, cfg, SERVE_PCFG))(
        jax.random.key(seed))


def make_engine(cfg, params, *, max_len: int, spamm_cfg=None,
                **engine_kw) -> Engine:
    """The serving engine on a one-device host mesh (pod-sharded serving
    builds its own mesh from `mesh_devices`)."""
    ctx = make_ctx(make_host_mesh())
    return Engine(cfg, SERVE_PCFG, ctx, params, max_len=max_len,
                  spamm_cfg=spamm_cfg, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="draw each request's prompt length uniformly from "
                         "[prompt_len/2, prompt_len] instead of one uniform "
                         "length — exercises the chunked slot scheduler "
                         "(attention stacks; implies chunked prefill)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: advance prompts C tokens per "
                         "engine step at ONE static shape, interleaved with "
                         "decode (C % spamm-tile == 0 when gating). Default "
                         "auto: chunk only for mixed-length batches; 0 "
                         "disables chunking (mixed lengths then rejected)")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="cap the chunked scheduler's concurrent slot pool "
                         "(power-of-two bucketed); below --num-requests the "
                         "queue drives admission into freed slots between "
                         "decode steps")
    ap.add_argument("--spamm-tau", type=float, default=None,
                    help="enable SpAMM norm-gated GEMMs at this τ — prefill "
                         "AND decode gate (decode through frozen plans); "
                         "one SpammContext per engine")
    ap.add_argument("--spamm-tile", type=int, default=None,
                    help="gating tile; default 128 on the pallas backend "
                         "(the compiled kernels need multiples of 128), 32 "
                         "on interpret/jnp")
    ap.add_argument("--spamm-backend", default="auto")
    ap.add_argument("--spamm-block-n", type=int, default=1,
                    help="super-column width of the mm kernel; must match "
                         "the value the plan store was precomputed with, or "
                         "every lookup misses and plans are rebuilt")
    ap.add_argument("--spamm-levels", type=int, default=0,
                    help="norm-pyramid coarsening steps for hierarchical "
                         "gating (0 = flat); coarse tile = tile · 2^levels")
    ap.add_argument("--spamm-dtype", default="float32",
                    choices=("float32", "bfloat16", "bf16", "int8"),
                    help="GEMM compute dtype for the gated GEMMs (f32 "
                         "accumulate; gate stays a conservative superset of "
                         "the f32 gate via the widened τ). Must match the "
                         "plan store's precompute dtype or every lookup "
                         "misses")
    ap.add_argument("--spamm-autotune", action="store_true",
                    help="roofline-autotune block_n/levels/bucket per weight "
                         "at freeze time (core.cost); --spamm-block-n/"
                         "--spamm-levels become the tuner's defaults. Must "
                         "match the plan store's precompute setting or "
                         "lookups miss (tuned params address the artifacts)")
    ap.add_argument("--spamm-tune-profile", default=None,
                    help="calibrated cost-profile JSON for --spamm-autotune "
                         "(benchmarks/autotune --calibrate)")
    ap.add_argument("--plan-store", default=None,
                    help="on-disk PlanStore directory of precomputed frozen "
                         "weight plans (populate offline with "
                         "repro.launch.precompute_plans); the engine warm-"
                         "starts from it instead of running a planning pass")
    ap.add_argument("--no-freeze-plans", action="store_true",
                    help="legacy in-trace gating (weight normmaps re-derived "
                         "inside the compiled prefill; decode GEMMs fall "
                         "back to dense — decode only gates through frozen "
                         "plans) instead of frozen plans as jit inputs")
    ap.add_argument("--reshard-every", type=int, default=0,
                    help="drift-triggered re-sharding probe cadence in "
                         "engine steps (prefill + decode); 0 = off; needs "
                         "--spamm-tau. The engine maintains the equal-work "
                         "row partition a pod feeds to "
                         "distributed.spamm_rowpart(offsets=)")
    ap.add_argument("--reshard-devices", type=int, default=0,
                    help="strips to cut (0 = the mesh's data-axis extent)")
    ap.add_argument("--reshard-threshold", type=float, default=1.2,
                    help="re-cut when the live partition's predicted "
                         "imbalance exceeds the fresh cut's by this factor")
    ap.add_argument("--reshard-level", type=int, default=0,
                    help="norm-pyramid level of the re-sharding probe "
                         "estimate (coarser = cheaper)")
    ap.add_argument("--spamm-mesh-devices", type=int, default=0,
                    help="pod-sharded serving: run the compiled steps under "
                         "shard_map over a 1-D mesh of this many devices, "
                         "the batch rows cut by the live equal-work offsets "
                         "(needs --spamm-tau + frozen plans; batch and "
                         "prompt length must be multiples of --spamm-tile)")
    ap.add_argument("--spamm-shard-width", type=int, default=0,
                    help="static per-shard width in request GROUPS (of "
                         "--spamm-tile requests each); 0 = 2·ceil(groups/"
                         "devices). Caps how far the equal-work cut can "
                         "skew without a recompile")
    ap.add_argument("--metrics-out", default=None,
                    help="write the run's metrics registry here as a "
                         "Prometheus text dump (TTFT/decode latency "
                         "histograms, per-layer gated-GEMM series, plan "
                         "cache/store and reshard counters)")
    ap.add_argument("--trace-out", default=None,
                    help="write the run's host-side spans here as Chrome-"
                         "trace JSON (freeze, plan assembly, prefill, "
                         "decode steps, reshard probes; load in Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, args.seed)
    spamm_cfg = None
    if args.spamm_tau is not None:
        backend = kops.resolve_backend(args.spamm_backend)
        tile = args.spamm_tile or default_tile(backend)
        print(f"spamm backend: {args.spamm_backend} -> {backend} "
              f"(tile {tile}, device {jax.devices()[0].device_kind})")
        spamm_cfg = SpammConfig(enable=True, tau=args.spamm_tau,
                                tile=tile,
                                backend=args.spamm_backend,
                                block_n=args.spamm_block_n,
                                levels=args.spamm_levels,
                                dtype=args.spamm_dtype,
                                autotune=args.spamm_autotune,
                                tune_profile=args.spamm_tune_profile)
    reshard_cfg = None
    if args.reshard_every > 0:
        if spamm_cfg is None:
            print("warning: --reshard-every needs --spamm-tau (the probe "
                  "estimates gated work); re-sharding stays OFF")
        from repro.core.schedule import ReshardConfig

        reshard_cfg = ReshardConfig(
            num_devices=args.reshard_devices, every=args.reshard_every,
            drift_threshold=args.reshard_threshold, level=args.reshard_level)
    from repro.obs import Observability

    obs = Observability(process_name="repro-serve")
    eng = make_engine(cfg, params, max_len=args.max_len,
                      spamm_cfg=spamm_cfg, plan_store=args.plan_store,
                      freeze_plans=not args.no_freeze_plans,
                      reshard_cfg=reshard_cfg,
                      mesh_devices=args.spamm_mesh_devices,
                      shard_max_width=args.spamm_shard_width or None,
                      prefill_chunk=args.prefill_chunk,
                      max_slots=args.max_slots,
                      obs=obs)

    rng = np.random.default_rng(args.seed)
    if args.mixed_lengths:
        plens = rng.integers(max(1, args.prompt_len // 2),
                             args.prompt_len + 1,
                             size=args.num_requests)
    else:
        plens = np.full(args.num_requests, args.prompt_len)
    reqs = [
        Request(
            prompt=rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for n in plens
    ]
    t0 = time.time()
    outs = eng.generate(reqs)
    dt = time.time() - t0
    total = sum(len(o) for o in outs)
    print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s)")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o[:12].tolist()}")
    sp = reqs[0].out.get("spamm") if reqs[0].out else None
    if sp is not None:
        vf = sp["valid_fraction"]
        vf_s = f"{vf:.3f}" if vf is not None else "n/a"
        dvf = sp.get("decode_valid_fraction")
        dvf_s = f"{dvf:.3f}" if dvf is not None else "n/a"
        print(f"  spamm: valid_fraction={vf_s} gated_gemms={sp['gated_gemms']} "
              f"decode_valid_fraction={dvf_s} "
              f"decode_gated_gemms={sp['decode_gated_gemms']} "
              f"cache={sp['plan_cache_hits']}h/{sp['plan_cache_misses']}m")
        lat = sp.get("latency")
        if lat is not None:
            # engine-measured per-phase latency (TTFT from wave start to
            # first token; decode stats over the wave's inter-token gaps)
            ttft = lat.get("ttft_s")
            line = (f"  latency: ttft="
                    + (f"{ttft * 1e3:.1f}ms" if ttft is not None else "n/a"))
            if lat.get("decode_steps"):
                line += (f" decode mean={lat['decode_mean_s'] * 1e3:.1f}ms"
                         f" p50={lat['decode_p50_s'] * 1e3:.1f}ms"
                         f" p95={lat['decode_p95_s'] * 1e3:.1f}ms"
                         f" ({lat['decode_steps']} steps)")
            print(line)
        cres = sp.get("cost_residual")
        if cres:
            # uncalibrated coefficients are a nominal table, not a fit to
            # this device: their predictions are not device numbers
            coeffs = eng.spamm_ctx.cost_coeffs
            src = ("calibrated" if coeffs is not None and coeffs.calibrated
                   else "nominal coefficients, not a device measurement")
            for phase, c in cres.items():
                print(f"  cost[{phase}]: predicted={c['predicted_s']:.4f}s "
                      f"({src}) measured={c['measured_s']:.4f}s "
                      f"log2_residual={c['log2_ratio']:+.2f}")
        gb = sp.get("gemm_bytes_moved")
        dgb = sp.get("decode_gemm_bytes_moved")
        if gb is not None or dgb is not None:
            gb_s = f"{gb/1e6:.3f}MB" if gb is not None else "n/a"
            dgb_s = f"{dgb/1e6:.3f}MB" if dgb is not None else "n/a"
            print(f"  spamm dtype={sp.get('compute_dtype', 'float32')}: "
                  f"prefill_gemm_bytes={gb_s} decode_gemm_bytes={dgb_s}")
        if "plan_store_hits" in sp:
            print(f"  plan_store: {sp['plan_store_hits']}h/"
                  f"{sp['plan_store_misses']}m")
        if "resharded" in sp:
            imb = sp["partition_imbalance"]
            imb_s = f"{imb:.3f}" if imb is not None else "n/a"
            print(f"  reshard: events={sp['resharded']} "
                  f"probes={sp['reshard_probes']} "
                  f"partition_imbalance={imb_s}")
            offs = eng.partition_offsets
            if offs is None:
                print("  partition: unsharded (no live cut yet)")
            else:
                offs = np.asarray(offs)
                rows = np.diff(offs)
                loads = eng._resharder.live_loads
                for d in range(rows.shape[0]):
                    ld = f"{loads[d]:.3f}" if loads is not None else "n/a"
                    print(f"    strip {d}: rows [{offs[d]}, {offs[d + 1]}) "
                          f"({int(rows[d])} rows) predicted_load={ld}")
        else:
            print("  partition: unsharded (no reshard controller attached)")
        lay = eng.shard_layout
        if lay is not None:
            # lockstep mesh: the per-step wall-clock is the slowest shard's;
            # the engine's own decode-step histogram is the measurement now
            # (reshard stalls included), the per-shard layout shows where
            # the rows sat
            o = lay["offsets"]
            ms = (lat or {}).get("decode_mean_s")
            ms_s = (f"{ms * 1e3:.1f} ms/step (lockstep)" if ms is not None
                    else "n/a ms/step")
            print(f"  pod-sharded over {args.spamm_mesh_devices} devices: "
                  f"{ms_s}, slot_width={lay['slot_width']} reqs/shard")
            for d, n in enumerate(lay["real"]):
                print(f"    shard {d}: reqs [{o[d]}, {o[d + 1]}) "
                      f"({n} live, {lay['slot_width'] - n} pad slots)")
    if args.metrics_out:
        print(f"metrics -> {obs.write_metrics(args.metrics_out)}")
    if args.trace_out:
        print(f"trace -> {obs.write_trace(args.trace_out)}")
    if args.metrics_out or args.trace_out:
        print(obs.summary_table())


if __name__ == "__main__":
    main()
