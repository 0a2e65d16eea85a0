"""FLOPs of the surviving tile products (2 x tile^3 each) of every
product in the window, over the window's elapsed time, over the bf16 peak
of the chips used (host clock). f32 at HIGHEST runs several bf16 passes,
so this cannot pass about a sixth."""


def read(run):
    if not run.products or not run.peaks:
        return None
    w = run.product_work
    flops = 2.0 * w["tile"] ** 3 * len(w["triples"][0]) * run.products
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * flops / run.window_s / peak
