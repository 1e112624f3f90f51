"""Scheduler: share of the packed rows of the window's steps that held no
live token (the layout's rows against decode tokens plus prefill chunk
tokens)."""


def read(run):
    rows = sum(s.t_pack for s in run.steps)
    if not rows:
        return None
    live = sum(q for s in run.steps for q, _ in s.decode + s.prefill)
    return 100.0 * (1.0 - live / rows)
