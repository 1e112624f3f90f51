"""Process start to the window's start: weights, engine, compiles or the
compile cache, warm-up and the traffic before the window."""


def read(run):
    return run.setup_s
