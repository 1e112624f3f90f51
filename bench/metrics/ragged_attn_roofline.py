"""Kernels: the ragged paged attention kernel's share of its roofline.
The least time the chip could take for each call (the larger of the
operations over peak FLOP/s and the bytes over peak bandwidth, from each
step's live segments: bench/work), summed over the traced window, over the
kernel's device time in the trace.  A mixed step calls the kernel twice
per layer (decode segments, prefill segments); each call is bounded on
its own."""

from bench.work import ragged_call_work, roofline_s


def read(run):
    if run.trace is None or not run.trace.kernel_s or not run.steps:
        return None
    layers = run.config["num_hidden_layers"]
    need = 0.0
    for s in run.steps:
        calls = [s.decode, s.prefill] if s.mixed else [s.decode]
        for segs in calls:
            f, b = ragged_call_work(segs, run.config, run.itemsize)
            need += layers * roofline_s(f, b, run.peak)
    return 100.0 * need / run.trace.kernel_s
