"""Scheduler: 95th percentile, over the requests due in the window, of
the engine's own admission time minus its submit time
(``Request.admit_t - Request.submit_t``): the wait in the engine's queue
as the engine records it.  One not admitted by the window's end counts at
the window's end.  None where the engine stamps no admission."""

from bench.stats import p95


def read(run):
    end = run.window_end
    waits = []
    for r in run.reqs:
        if not (run.t0 <= r.due < run.t_end) or r.submit is None:
            continue
        admit = getattr(r.engine, "admit_t", None)
        if admit is None:
            return None
        waits.append((admit if 0 < admit <= end else end)
                     - r.engine.submit_t)
    v = p95(waits)
    return None if v is None else 1e3 * v
