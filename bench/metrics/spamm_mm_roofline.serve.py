"""Share of the roofline reached by the tile-product kernels (`spamm_mm*`)
in the window: the least time the chip could take for the gated GEMMs the
waves required, over those kernels' device time (device trace). At
tau = 0 every tile triple survives, so the work is the dense GEMMs' on the
real rows (a decode step's 8 rows, not its padded row tile); for tau > 0
the surviving triples are needed and this reads nothing."""
from bench import devtrace
from bench.work import gated_gemm_cost, least_time_s, serve_gemm_shapes

PREFIXES = ("spamm_mm",)


def read(run):
    if run.trace is None or not run.waves or run.config["spamm"]["tau"] != 0:
        return None
    model, tile = run.config["model"], run.config["spamm"]["tile"]
    need = 0.0
    for w in run.waves:
        steps = [(w["batch"] * w["prompt_len"], 1),
                 (w["batch"], w["new_tokens"] - 1)]
        for rows, count in steps:
            for m, k, n in serve_gemm_shapes(model, rows):
                need += count * least_time_s(*gated_gemm_cost(m, k, n, tile),
                                             run.peaks)[0]
    t = run.trace
    ns = sum(devtrace.kernel_ns(ops, t["window"], PREFIXES)
             for ops in t["device_ops"].values())
    return 100.0 * need / (ns / 1e9) if ns else None
