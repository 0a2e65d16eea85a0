"""Output tokens returned by `generate` over the window's elapsed time
(host clock; the window holds whole waves)."""


def read(run):
    if not run.waves:
        return None
    return sum(w["out_tokens"] for w in run.waves) / run.window_s
