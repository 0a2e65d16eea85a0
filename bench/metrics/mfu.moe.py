"""Active FLOPs of every wave of the window (latent attention's
projections and core, the dense layer, the shared experts, the routers,
the expert GEMMs over the rows the engine routed to held experts, and the
unembedding of each returned token; `bench.moe_work`), over the window's
elapsed time, over the bf16 peak of the chips used (host clock). f32 at
HIGHEST runs several bf16 passes, so this cannot pass about a sixth."""
from bench.moe_work import wave_flops


def read(run):
    if not run.waves or not run.peaks:
        return None
    if any(not w.get("moe") for w in run.waves):
        return None
    model = run.config["model"]
    flops = sum(wave_flops(model, w) for w in run.waves)
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * flops / run.window_s / peak
