"""95th percentile, over the requests due in the window, of first token
minus due time.  A request with no first token by the window's end counts
at the window's end."""

from bench.stats import p95


def read(run):
    end = run.window_end
    v = p95((r.token_t[0] if r.token_t and r.token_t[0] <= end else end)
            - r.due for r in run.reqs if run.t0 <= r.due < run.t_end)
    return None if v is None else 1e3 * v
