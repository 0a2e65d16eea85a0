"""Share of the roofline reached by the tile-product kernels (`spamm_mm*`)
in the window: the least time the chip could take for the surviving tile
products of every product (operations over peak FLOP/s or the distinct A
and B tiles read plus C written over HBM bandwidth, the larger), over
those kernels' device time summed over the chips used (device trace)."""
from bench import devtrace
from bench.work import gated_gemm_cost, least_time_s

PREFIXES = ("spamm_mm",)


def read(run):
    if run.trace is None or not run.products:
        return None
    w = run.product_work
    ii, jj, kk = w["triples"]
    cost = gated_gemm_cost(w["n"], w["n"], w["n"], w["tile"], ii=ii, jj=jj,
                           kk=kk)
    need = least_time_s(*cost, run.peaks)[0] * run.products
    t = run.trace
    ns = sum(devtrace.kernel_ns(ops, t["window"], PREFIXES)
             for ops in t["device_ops"].values())
    return 100.0 * need / (ns / 1e9) if ns else None
