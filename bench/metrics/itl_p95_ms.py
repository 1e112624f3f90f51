"""95th percentile of every gap between consecutive output tokens of
every request, both tokens inside the window: one pool of gaps."""

from bench.stats import p95


def read(run):
    gaps = [b - a for r in run.reqs for a, b in zip(r.token_t, r.token_t[1:])
            if a >= run.t0]
    v = p95(gaps)
    return None if v is None else 1e3 * v
