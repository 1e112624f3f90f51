"""Load generator: 95th percentile of submit time minus due time over the
requests due in the window (an open loop submits between steps, so a
request that comes due during a step waits for it)."""

from bench.stats import p95


def read(run):
    v = p95(r.submit - r.due for r in run.reqs
            if run.t0 <= r.due < run.t_end and r.submit is not None)
    return None if v is None else 1e3 * v
