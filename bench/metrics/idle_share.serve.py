"""Share of the window in which the device ran no op: 1 - union of the
device op intervals over the window, the mean over the chips used
(device trace)."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.waves:
        return None
    return 100.0 * devtrace.idle_share(run.trace["device_ops"],
                                       run.trace["window"])
