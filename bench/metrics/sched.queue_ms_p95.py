"""Scheduler: 95th percentile, over the requests due in the window, of
submit time to the start of the first step that packed them: the wait in
the engine's queue, after the load generator's lag (entry) and before the
request's prefill.  One not packed by the window's end counts at the
window's end."""

from bench.stats import p95


def read(run):
    end = run.window_end
    v = p95((r.packed if r.packed is not None and r.packed <= end else end)
            - r.submit for r in run.reqs
            if run.t0 <= r.due < run.t_end and r.submit is not None)
    return None if v is None else 1e3 * v
