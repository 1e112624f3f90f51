"""Model step, mixed profile: mean device time of the mixed decode and
prefill step program, from the trace."""


def read(run):
    t = run.trace and run.trace.step_ms(run.steps, mixed=True)
    return t or None
