"""Dense model FLOPs of every prompt and generated token the window
processed (2 x matmul parameters per token, attention over the context,
the unembedding of each token returned), over the window's elapsed time,
over the bf16 peak of the chips used (host clock). f32 at HIGHEST runs
several bf16 passes, so this cannot pass about a sixth."""
from bench.work import wave_flops


def read(run):
    if not run.waves or not run.peaks:
        return None
    model = run.config["model"]
    flops = sum(wave_flops(model, w["batch"], w["prompt_len"],
                           w["new_tokens"]) for w in run.waves)
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * flops / run.window_s / peak
