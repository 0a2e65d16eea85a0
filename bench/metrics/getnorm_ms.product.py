"""Device time of the get-norm kernels (`spamm_getnorm*`, `spamm_norm*`)
per product in the window, summed over the chips used (device trace)."""
from bench import devtrace

PREFIXES = ("spamm_getnorm", "spamm_norm")


def read(run):
    if run.trace is None or not run.products:
        return None
    t = run.trace
    ns = sum(devtrace.kernel_ns(ops, t["window"], PREFIXES)
             for ops in t["device_ops"].values())
    return ns / 1e6 / run.products if ns else None
