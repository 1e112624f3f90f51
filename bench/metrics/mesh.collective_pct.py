"""Mesh: chip 0's leaf-op time in collective ops, as a share of all its
leaf-op time in the traced window (its busy time, each op counted once).

A collective is found by its label in ``run.trace.ops`` (``bench.trace.
op_label``: the HLO instruction's name).  None without a trace or where
the window ran no collective."""

COLLECTIVE_LABELS = frozenset([
    # a traced v5e-4 run of deepseek-7b-tp4.chat: the TPU compiler keeps
    # the name JAX gives a tensor-parallel all-reduce (``%psum.<n> = ...
    # all-reduce(...)``)
    "psum",
    # the same step compiled for a v5e:2x2: the logits gather is
    # ``%all-gather.<n>``
    "all-gather",
    # XLA's names for these collectives where the compiler renames them,
    # splits them into async halves, or rewrites an all-reduce
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather-start", "all-gather-done",
    "collective-permute", "reduce-scatter",
])


def read(run):
    if run.trace is None:
        return None
    total = sum(run.trace.ops.values())
    coll = sum(s for label, s in run.trace.ops.items()
               if label in COLLECTIVE_LABELS)
    if total <= 0 or coll <= 0:
        return None
    return 100.0 * coll / total
