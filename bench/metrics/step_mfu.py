"""Model step: the model FLOPs the traced window's tokens need (bench/work)
over the traced window times the chips' bf16 peak."""

from bench.work import step_flops


def read(run):
    if run.trace is None or not run.steps:
        return None
    flops = sum(step_flops(s.decode + s.prefill, s.sampled, run.config)
                for s in run.steps)
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops"]
                            * run.chips)
