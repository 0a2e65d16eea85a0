"""Window elapsed over products completed; each product ends in
`block_until_ready` (host clock)."""


def read(run):
    if not run.products:
        return None
    return run.window_s / run.products * 1e3
