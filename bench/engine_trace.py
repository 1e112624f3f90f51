"""What a JAX profiler trace of the serving engine says of the engine's
own spans and the model step's named scopes, beside ``bench/trace.py``.

``bench/trace.py`` reduces a traced run to the harness's metrics and is
left as it is; this module reads the same ``.xplane.pb`` for what the
program records of itself:

* the engine's host spans, opened by ``ServeEngine.step`` on the
  profiler's clock: ``engine.step`` (``step=<n>``) and inside it
  ``engine.admit``, ``engine.pages``, ``engine.pack``, ``engine.upload``,
  ``engine.dispatch``, ``engine.pull`` and ``engine.commit``;
* each device op's scope path (``jax.named_scope``: ``attn`` with
  ``kv_write`` inside it, ``mlp``, ``head``, ``sample``), the ``tf_op``
  stat of the op's event metadata, e.g. ``jit(<unknown>)/while/body/
  closed_call/attn/kv_write/scatter:``.  ``ProfileData`` does not expose
  metadata stats, so :func:`chip_ops` reads them from the serialized
  XSpace.

The ragged attention kernel is the ``pallas_call`` named
``ragged_paged_attention``: its ops read ``%ragged_paged_attention.<n> =
... custom-call(...)`` and keep ``custom_call_target="tpu_custom_call"``,
so ``bench.trace.KERNEL_MARK`` still finds it.

Over the harness's ``bench.window`` span, on chip 0:

* scope time: leaf ops by the outermost of ``SCOPES`` in their scope
  path, else ``other``; and the time under ``kv_write``;
* engine time: host seconds in each ``engine.*`` span;
* engine idle: the device's idle stretches, each credited whole to the
  ``engine.*`` span the host was innermost in for most of it (a stretch
  outside every engine span is not credited).

The harness does not call this yet: a traced run deletes its trace after
``bench.trace.reduce_dir``.  :func:`reduce_dir` reads a trace directory
of the same layout.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

from .trace import _DEVICE, _clip, _leaves

#: the model step's named scopes that split its device time
SCOPES = ("attn", "mlp", "head", "sample")


@dataclass
class EngineSummary:
    window_s: float
    #: chip 0's leaf-op seconds by scope (``SCOPES`` and ``other``; empty
    #: where no op carries one of ``SCOPES``), and under ``kv_write``
    scopes: dict = field(default_factory=dict)
    kv_write_s: float = 0.0
    engine: dict = field(default_factory=dict)  # engine span -> host s
    engine_idle: dict = field(default_factory=dict)  # span -> idle s
    engine_steps: list = field(default_factory=list)  # step= in window
    #: the window's longest engine.step: its step=, seconds, and the
    #: seconds of each engine span inside it
    engine_slowest: dict = field(default_factory=dict)


def reduce(pd, raw: bytes | None = None) -> EngineSummary:
    """Reduce a ``jax.profiler.ProfileData`` to an :class:`EngineSummary`.
    With ``raw``, the serialized XSpace ``pd`` was read from, chip 0's
    device time is also split by scope."""
    window, ops, engine = None, [], []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and m.group(1) == "0" and line.name == "XLA Ops":
                ops = [(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, e.name)
                       for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    s, t = e.start_ns * 1e-9, (e.start_ns
                                               + e.duration_ns) * 1e-9
                    if e.name == "bench.window" and window is None:
                        window = (s, t)
                    elif e.name.startswith("engine."):
                        engine.append((s, t, e.name,
                                       dict(e.stats).get("step")))
    if window is None or not ops:
        raise ValueError("the trace has no bench.window span or no TPU "
                         "op events")
    lo, hi = window
    out = EngineSummary(window_s=hi - lo)
    engine = [sp for sp in engine if sp[1] > lo and sp[0] < hi]
    for s, e, n in _clip([sp[:3] for sp in engine], lo, hi):
        out.engine[n] = out.engine.get(n, 0.0) + (e - s)
    out.engine_steps = [step for s, _, n, step in sorted(engine)
                        if n == "engine.step" and lo <= s < hi]
    steps = [sp for sp in engine if sp[2] == "engine.step"]
    if steps:
        s0, e0, _, n0 = max(steps, key=lambda sp: sp[1] - sp[0])
        inner: dict[str, float] = {}
        for s, e, n, _ in engine:
            if n != "engine.step" and s0 <= s and e <= e0:
                inner[n] = inner.get(n, 0.0) + (e - s)
        out.engine_slowest = {"step": n0, "s": e0 - s0, "spans": inner}
    leaves = _leaves(_clip(ops, lo, hi))
    out.engine_idle = _idle_by_engine_span(
        leaves, [sp[:3] for sp in engine], lo, hi)
    if raw is not None:
        out.scopes, out.kv_write_s = _scope_seconds(chip_ops(raw, 0),
                                                    lo, hi)
    return out


def _gaps(leaves, lo, hi) -> list:
    """The (start, end) stretches of ``[lo, hi]`` with no leaf op."""
    gaps, t = [], lo
    for s, e, _ in sorted(leaves):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _innermost(spans, g0, g1) -> dict:
    """Seconds of ``[g0, g1]`` in which each span is the innermost (the
    shortest) of ``spans`` holding the instant."""
    cuts = sorted({g0, g1, *(t for s, e, _ in spans for t in (s, e)
                             if g0 < t < g1)})
    own: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        holding = [sp for sp in spans if sp[0] <= a and b <= sp[1]]
        if holding:
            sp = min(holding, key=lambda sp: sp[1] - sp[0])
            own[sp] = own.get(sp, 0.0) + (b - a)
    return own


def _idle_by_engine_span(leaves, spans, lo, hi) -> dict:
    """Idle stretches of the device, each credited whole to the engine
    span the host was innermost in for most of it; a stretch outside
    every engine span is not credited."""
    spans = sorted(spans)
    out: dict[str, float] = {}
    j, live = 0, []
    for g0, g1 in _gaps(leaves, lo, hi):  # a sweep: both in time order
        while j < len(spans) and spans[j][0] < g1:
            live.append(spans[j])
            j += 1
        live = [sp for sp in live if sp[1] > g0]
        own = _innermost(live, g0, g1)
        if own:
            name = max(own, key=own.get)[2]
            out[name] = out.get(name, 0.0) + (g1 - g0)
    return out


def _scope_seconds(events, lo, hi) -> tuple[dict, float]:
    """Leaf-op seconds in ``[lo, hi]`` by the outermost of ``SCOPES`` in
    each op's scope path (``other`` for none), and the seconds under
    ``kv_write``.  Empty where no op carries one of ``SCOPES``."""
    out: dict[str, float] = {}
    kv = 0.0
    for s, e, path in _leaves(_clip(events, lo, hi)):
        parts = path.rstrip(":").split("/")
        key = next((p for p in parts if p in SCOPES), "other")
        out[key] = out.get(key, 0.0) + (e - s)
        if "kv_write" in parts:
            kv += e - s
    if not any(k in out for k in SCOPES):
        return {}, 0.0
    return out, kv


# -- the serialized XSpace, for what ProfileData does not expose -------------
# (tsl/profiler/protobuf/xplane.proto: XSpace.planes 1; XPlane name 2,
# lines 3, event_metadata 4, stat_metadata 5; XLine name 2, timestamp_ns 3,
# events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3;
# XEventMetadata stats 5; XStat metadata_id 1, str_value 5, ref_value 7;
# XStatMetadata name 2; a map entry is key 1, value 2)

def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one protobuf message: an
    int for a varint, a memoryview of the bytes for the rest."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map(plane_fields, number: int) -> dict:
    """An XPlane map field: key -> the value message's fields."""
    out = {}
    for k, v in plane_fields:
        if k == number:
            entry = dict(_fields(v))
            out[entry.get(1, 0)] = list(_fields(entry.get(2, b"")))
    return out


def chip_ops(raw: bytes, chip: int) -> list:
    """(start s, end s, scope path) of each ``XLA Ops`` event of one chip,
    from the serialized XSpace: the scope path is the ``tf_op`` stat of
    the event's metadata ('' where there is none)."""
    for k, plane in _fields(raw):
        if k != 1:
            continue
        pf = list(_fields(plane))
        if _text(dict(pf).get(2, b"")) != f"/device:TPU:{chip}":
            continue
        stat_names = {i: _text(dict(f).get(2, b""))
                      for i, f in _map(pf, 5).items()}
        tf_op = [i for i, n in stat_names.items() if n == "tf_op"]
        paths = {}
        for i, f in _map(pf, 4).items():
            for sk, sv in f:
                st = dict(_fields(sv)) if sk == 5 else {}
                if tf_op and st.get(1) == tf_op[0]:
                    paths[i] = (_text(st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
        out = []
        for lk, line in pf:
            lf = list(_fields(line)) if lk == 3 else []
            if not lf or _text(dict(lf).get(2, b"")) != "XLA Ops":
                continue
            t0 = dict(lf).get(3, 0)
            for ek, ev in lf:
                if ek == 4:  # whole ns, as ProfileData gives them
                    e = dict(_fields(ev))
                    s = t0 + e.get(2, 0) // 1000
                    out.append((s * 1e-9, (s + e.get(3, 0) // 1000) * 1e-9,
                                paths.get(e.get(1, 0), "")))
        return out
    return []


def reduce_dir(path: str) -> EngineSummary:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}: {files}")
    with open(files[0], "rb") as f:
        raw = f.read()
    return reduce(ProfileData.from_serialized_xspace(raw), raw)
