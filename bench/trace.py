"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds (JAX 0.9, libtpu 0.0.34): a plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` has one event per
executed HLO op, named by the op's HLO text, and whose line ``XLA
Modules`` has one event per program run; host planes (``/host:CPU``)
carry the ``jax.profiler.TraceAnnotation`` spans.  Control-flow ops
(the layer scan's ``while``) enclose the ops they run, so only leaf ops
count towards per-op time.

The ragged attention kernel has no stable name of its own in the trace:
it is the step's Pallas ``tpu_custom_call`` (``KERNEL_MARK``).

* busy: union of a chip's op intervals inside the traced window
  (``bench.window`` span), averaged over the chips used;
* kernel time: summed device time of the kernel's op events on chip 0;
* step program time: per host ``bench.step`` span, the longest program
  (``XLA Modules``) event on chip 0 that starts inside it;
* idle gaps: the parts of the window on chip 0 with no op running, each
  credited to the harness span the host was in (``bench.step``,
  ``bench.wait``, ...) or ``host.other``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

from .stats import union_length

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over chips
    kernel_s: float  # chip 0
    step_programs: list = field(default_factory=list)  # seconds per step
    ops: dict = field(default_factory=dict)  # leaf op label -> seconds
    idle: dict = field(default_factory=dict)  # host span -> idle seconds

    def step_ms(self, steps, mixed: bool) -> float | None:
        """Mean device time of the step program over the steps of one
        profile, where every step of the window was matched."""
        if len(self.step_programs) != len(steps):
            return None
        t = [p for p, s in zip(self.step_programs, steps)
             if s.mixed == mixed and p is not None]
        return 1e3 * sum(t) / len(t) if t else None

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_label(name: str) -> str:
    """A short label for an op event named by its HLO text: the kernel,
    or the HLO op kind (``fusion``, ``sort``, ``copy-start`` ...)."""
    if KERNEL_MARK in name:
        return "ragged_attn (tpu_custom_call)"
    m = re.match(r"^%([A-Za-z_\-]+?)(?:[.\d]*)\s*=", name)
    return m.group(1) if m else name[:48]


def _leaves(events):
    """(start, end, name) events that enclose no other event."""
    events = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    out = []
    for i, (s, e, n) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue  # encloses the next event: a control-flow op
        out.append((s, e, n))
    return out


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce(pd, chips: int) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Summary`."""
    spans, ops, modules = [], {}, {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name) for e in line.events]
            if m and line.name == "XLA Ops":
                ops[int(m.group(1))] = evs
            elif m and line.name == "XLA Modules":
                modules[int(m.group(1))] = evs
            elif plane.name.startswith("/host:"):
                spans += [ev for ev in evs if ev[2].startswith("bench.")]
    win = [s for s in spans if s[2] == "bench.window"]
    if not win or 0 not in ops:
        raise ValueError("the trace has no bench.window span or no TPU "
                         "op events")
    lo, hi = win[0][0], win[0][1]
    busy = [union_length((s, e) for s, e, _ in _clip(ops.get(c, []), lo, hi))
            for c in range(chips)]
    leaves = _leaves(_clip(ops[0], lo, hi))
    per_op: dict[str, float] = {}
    for s, e, n in leaves:
        per_op[op_label(n)] = per_op.get(op_label(n), 0.0) + (e - s)
    kernel = sum(e - s for s, e, n in leaves if KERNEL_MARK in n)
    steps = sorted(s for s in spans if s[2] == "bench.step"
                   and lo <= s[0] < hi)
    progs = _clip(modules.get(0, []), lo, hi)
    step_programs = []
    for s, e, _ in steps:
        inside = [pe - ps for ps, pe, _ in progs if s <= ps < e]
        step_programs.append(max(inside) if inside else None)
    idle = _idle_by_span(leaves, [s for s in spans if s[2] != "bench.window"],
                         lo, hi)
    return Summary(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                   kernel_s=kernel, step_programs=step_programs,
                   ops=per_op, idle=idle)


def _idle_by_span(leaves, spans, lo, hi) -> dict:
    """Idle stretches of the device, credited to the host span that
    overlaps each most."""
    gaps, t = [], lo
    for s, e, _ in sorted(leaves):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        best, name = 0.0, "host.other"
        for s, e, n in spans:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, name = ov, n
        out[name] = out.get(name, 0.0) + (g1 - g0)
    return out


def reduce_dir(path: str, chips: int) -> Summary:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}: {files}")
    return reduce(ProfileData.from_file(files[0]), chips)
