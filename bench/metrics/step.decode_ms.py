"""Model step, decode profile: mean device time of the decode-only step
program, from the trace."""


def read(run):
    t = run.trace and run.trace.step_ms(run.steps, mixed=False)
    return t or None
