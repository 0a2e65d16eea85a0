"""Fault-tolerant training loop (deliverable b/e substrate).

Features exercised by tests/examples on CPU and designed for pods:
  * checkpoint/restart: atomic snapshots every `ckpt_every`, resume-from-
    latest restores params/opt/step and the data stream position;
  * straggler watchdog: per-step wall time vs. rolling median — steps slower
    than `straggler_factor`× median are counted and logged (on a pod this
    feeds the controller's replace-node decision);
  * simulated failure injection (`fail_at_step`) to test the restart path;
  * optional int8+error-feedback gradient compression.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.core import module as spmod
from repro.core import schedule as _schedule
from repro.data.pipeline import SyntheticLM
from repro.distributed.compression import Int8EF
from repro.models import model as M
from repro.models.transformer import NetCtx
from repro.obs import FRACTION_BUCKETS, LATENCY_BUCKETS_S, Observability
from repro.optim.adamw import AdamW


@dataclasses.dataclass
class TrainResult:
    losses: list
    restarts: int
    straggler_steps: int
    final_step: int
    # per-step SpAMM gating stats, one entry per executed step (the same
    # stats the serving engine attaches to Request.out["spamm"]): list of
    # {"step", "valid_fraction", "gated_gemms"} dicts, empty when SpAMM off.
    # Each entry also carries "per_layer": {layer: {valid_fraction,
    # gated_gemms}} — the grad-safe trace-buffer tier threads the per-layer
    # sums through the scan carry, so the breakdown survives value_and_grad.
    # With re-sharding on, each entry also carries the live equal-work
    # partition's predicted "imbalance" (the drift series — None until the
    # first probe) and the cumulative "resharded" event count
    spamm_stats: list = dataclasses.field(default_factory=list)
    # the run's Observability bundle (registry with train_step_seconds /
    # spamm_valid_fraction series, spans around probe + checkpoint I/O) —
    # what launch.train exports via --metrics-out/--trace-out
    obs: Optional[Observability] = None


def train(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    tcfg: TrainConfig,
    ctx: NetCtx,
    *,
    global_batch: int = 8,
    seq_len: int = 128,
    spamm_cfg=None,
    reshard_cfg: Optional[_schedule.ReshardConfig] = None,
    fail_at_step: Optional[int] = None,
    resume: bool = False,
    straggler_factor: float = 3.0,
    log_every: int = 10,
    obs=None,
) -> TrainResult:
    obs = Observability.ensure(obs, process_name="repro-train")
    # step wall-clock lands in the registry (monotonic perf_counter — the
    # old time.time() readout jumped with NTP slews); keep_recent=50 retains
    # the raw samples the straggler watchdog's rolling median reads
    step_h = obs.registry.histogram(
        "train_step_seconds", "optimizer step wall-clock (dispatch + block)",
        buckets=LATENCY_BUCKETS_S, keep_recent=50)
    compression = (
        Int8EF() if pcfg.grad_compression == "int8_ef" else None
    )
    opt = AdamW(tcfg, compression=compression)
    data = SyntheticLM(cfg, global_batch, seq_len, seed=tcfg.seed)

    start_step = 0
    if resume and (last := ckpt.latest_step(tcfg.ckpt_dir)) is not None:
        like = {
            "params": jax.eval_shape(
                lambda k: M.init_params(cfg, pcfg, k), jax.random.key(tcfg.seed)
            ),
        }
        params = ckpt.restore(tcfg.ckpt_dir, last, like)["params"]
        params = jax.tree.map(jnp.asarray, params)
        opt_state = opt.init(params)  # moments restored below if present
        try:
            like_full = {"params": like["params"], "opt_state": jax.eval_shape(opt.init, like["params"])}
            full = ckpt.restore(tcfg.ckpt_dir, last, like_full)
            params = jax.tree.map(jnp.asarray, full["params"])
            opt_state = jax.tree.map(jnp.asarray, full["opt_state"])
        except KeyError:
            pass
        start_step = last
    else:
        params = M.init_params(cfg, pcfg, jax.random.key(tcfg.seed))
        opt_state = opt.init(params)

    # one context for the whole run, so train steps export the SAME gating
    # stats the serving engine attaches to Request.out["spamm"] — carried as
    # step METRICS (loss_fn threads them through the scan carry as values,
    # which survive grad)
    spamm_ctx = spmod.as_context(spamm_cfg)
    collect_spamm = spamm_ctx is not None and spamm_ctx.enable
    step_fn = jax.jit(M.make_train_step(cfg, pcfg, ctx, opt, spamm_cfg=spamm_ctx))

    # drift-triggered re-sharding (control plane, same contract as the
    # serving engine): every reshard_cfg.every steps re-probe the coarse V
    # estimate — fresh activation-side norms of the step's token embeddings
    # against the CACHED weight-side norms of the probe weight — and re-cut
    # the equal-work partition when the live cut's predicted imbalance
    # drifts past the fresh cut's. Never touches the computed values.
    resharder = None
    if reshard_cfg is not None and collect_spamm and reshard_cfg.every > 0:
        resharder = _schedule.ReshardController(
            _schedule.resolve_reshard_devices(reshard_cfg, ctx.mesh,
                                              ctx.batch_axes))

    def probe_reshard(step, batch):
        # `model.reshard_probe` is the shared probe body (same drift
        # behavior as the serving engine); frontend archs feed embedding
        # rows directly instead of tokens
        if "tokens" in batch:
            M.reshard_probe(resharder, spamm_ctx, params, step,
                            tokens=np.asarray(batch["tokens"]).reshape(-1))
        else:
            M.reshard_probe(resharder, spamm_ctx, params, step,
                            x=jnp.asarray(batch["embeds"]).reshape(
                                -1, cfg.d_model))

    losses, spamm_stats = [], []
    stragglers = 0
    restarts = 1 if resume and start_step else 0
    step = start_step
    m_vf = (obs.registry.histogram(
        "spamm_valid_fraction", labelnames=("phase", "layer", "site"),
        buckets=FRACTION_BUCKETS) if obs.enabled and collect_spamm else None)
    while step < tcfg.total_steps:
        if fail_at_step is not None and step == fail_at_step:
            raise RuntimeError(f"injected failure at step {step}")
        batch = data.batch_at(step)
        t0 = time.perf_counter()
        with obs.span("train_step", step=step):
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, jnp.int32(step)
            )
            loss = float(metrics["loss"])
        if resharder is not None and resharder.due(step):
            with obs.span("reshard_probe", step=step):
                probe_reshard(step, batch)
            if obs.enabled:
                resharder.publish(obs.registry)
        sp = None
        if collect_spamm and "spamm_valid_fraction" in metrics:
            n_gemms = int(metrics["spamm_gated_gemms"])
            sp = {"step": step,
                  "valid_fraction": (float(metrics["spamm_valid_fraction"])
                                     if n_gemms else None),
                  "gated_gemms": n_gemms}
            if "spamm_layer_valid_fraction" in metrics:
                lvf = np.asarray(metrics["spamm_layer_valid_fraction"])
                lvc = np.asarray(metrics["spamm_layer_gated_gemms"])
                sp["per_layer"] = {
                    int(i): {"valid_fraction": (float(lvf[i]) if lvc[i]
                                                else None),
                             "gated_gemms": int(lvc[i])}
                    for i in range(lvf.shape[0])}
                if m_vf is not None:
                    for i in range(lvf.shape[0]):
                        if lvc[i]:
                            m_vf.observe(float(lvf[i]), phase="train",
                                         layer=int(i), site="")
            if resharder is not None:
                sp["imbalance"] = resharder.live_imbalance
                sp["resharded"] = resharder.resharded
                # same readout the sharded serving engine places by: the
                # live cut and its per-strip predicted loads
                offs = resharder.offsets
                loads = resharder.live_loads
                sp["offsets"] = (None if offs is None
                                 else [int(o) for o in np.asarray(offs)])
                sp["loads"] = (None if loads is None
                               else [float(x) for x in loads])
            spamm_stats.append(sp)
        dt = time.perf_counter() - t0
        step_h.observe(dt)
        # straggler watchdog: rolling median over the histogram's retained
        # raw samples (keep_recent=50) — the registry is the one owner of
        # step durations now, no shadow list to drift out of sync
        med = float(np.median(step_h.recent()))
        if step_h.count() > 5 and dt > straggler_factor * med:
            stragglers += 1
        losses.append(loss)
        if log_every and step % log_every == 0:
            extra = ""
            if sp is not None and sp["valid_fraction"] is not None:
                extra = (f" spamm_valid {sp['valid_fraction']:.3f} "
                         f"({sp['gated_gemms']} gemms)")
            print(f"step {step:5d} loss {loss:.4f} ({dt*1e3:.0f} ms){extra}",
                  flush=True)
        step += 1
        if tcfg.ckpt_every and step % tcfg.ckpt_every == 0:
            with obs.span("checkpoint_save", step=step):
                ckpt.save(
                    tcfg.ckpt_dir, step,
                    {"params": params, "opt_state": opt_state},
                    async_=False,
                )
    return TrainResult(losses, restarts, stragglers, step, spamm_stats,
                       obs=obs)
