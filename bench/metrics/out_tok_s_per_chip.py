"""Output tokens the window's steps returned, per second of the window,
per chip."""


def read(run):
    n = sum(1 for r in run.reqs for t in r.token_t if t >= run.t0)
    return n / run.window_s / run.chips
