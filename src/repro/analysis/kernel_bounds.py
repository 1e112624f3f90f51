"""RPL3xx — Pallas kernel bounds: concrete BlockSpec validation.

AST inspection cannot prove a scalar-prefetched index map in bounds —
``pt[sh // hkv, j]`` depends on the page-table *values*.  So this pass
checks the property the TPU guide states but nothing enforces: every
block an index map selects, over the *entire grid*, must lie inside its
operand.  It does this concretely:

  1. ``jax.experimental.pallas.pallas_call`` is monkey-patched with a
     recorder; instead of lowering, it captures the grid spec, kernel,
     out_shape and — when the returned callable is invoked — the actual
     operands, then returns zeros of ``out_shape`` so the wrapper's
     surrounding ``jnp`` plumbing still runs.
  2. each registered *case* (a thunk invoking a kernel wrapper with the
     same shapes the tier-1 tests use) is executed under the recorder.
  3. for every captured call, every ``BlockSpec`` index map is evaluated
     at every grid point, with the real scalar-prefetch operands (page
     tables, segment tables) passed through — exactly what the Mosaic
     pipeline does at DMA-issue time.

Checks per captured call:

  * **RPL301** — a selected block (``index * block_shape`` for
    ``block_shape`` elements) escapes the operand, at any grid point.
  * **RPL302** — a block shape that does not tile its operand shape.
  * **RPL303** — kernel positional arity != scalar-prefetch count +
    inputs + outputs + scratch shapes (a kernel that takes ``*refs``
    may name fewer).  A spec with no block shape (an operand left whole,
    e.g. in HBM for the kernel's own copies) has no index map to check.
  * **RPL304** — array operands (ndim >= 3; scalar tables ride along as
    2-D int32/float32) disagree on dtype, or the out_shape dtype does.

The default case registry mirrors ``tests/test_kernels.py`` shapes for
``pallas_decode_attention``, ``pallas_paged_decode_attention`` and
``pallas_ragged_paged_attention`` — including partial last pages, null
pages and inactive (``q_len == 0``) segments.
"""

from __future__ import annotations

import functools
import inspect
import itertools
import os
import sys
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from .findings import Finding


@dataclass
class CapturedCall:
    kernel: Any
    path: str
    line: int
    grid: tuple
    in_specs: list
    out_specs: Any
    num_scalar_prefetch: int
    scratch_shapes: tuple
    out_shape: Any
    operands: tuple = ()
    case: str = ""


def _call_site() -> tuple[str, int]:
    """Innermost non-analysis frame: the wrapper's ``pl.pallas_call``."""
    f = sys._getframe(2)
    here = os.path.dirname(__file__)
    while f is not None and os.path.dirname(f.f_code.co_filename) == here:
        f = f.f_back
    if f is None:  # pragma: no cover
        return "<unknown>", 0
    path = f.f_code.co_filename
    try:
        path = os.path.relpath(path)
    except ValueError:  # pragma: no cover - different drive on win
        pass
    return path, f.f_lineno


@contextmanager
def capture_pallas_calls(captured: list[CapturedCall]):
    """Swap ``pallas_call`` for a recorder for the duration."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl_mod

    real = pl_mod.pallas_call

    def fake(kernel, *, out_shape=None, grid_spec=None, grid=None,
             in_specs=None, out_specs=None, scratch_shapes=(),
             interpret=False, **kw):
        path, line = _call_site()
        if grid_spec is not None:
            cap = CapturedCall(
                kernel=kernel, path=path, line=line,
                grid=tuple(grid_spec.grid),
                in_specs=list(grid_spec.in_specs),
                out_specs=grid_spec.out_specs,
                num_scalar_prefetch=getattr(grid_spec,
                                            "num_scalar_prefetch", 0),
                scratch_shapes=tuple(grid_spec.scratch_shapes or ()),
                out_shape=out_shape)
        else:
            cap = CapturedCall(
                kernel=kernel, path=path, line=line,
                grid=tuple(grid) if grid is not None else (),
                in_specs=list(in_specs or []), out_specs=out_specs,
                num_scalar_prefetch=0,
                scratch_shapes=tuple(scratch_shapes or ()),
                out_shape=out_shape)

        def runner(*ops):
            cap.operands = tuple(np.asarray(o) for o in ops)
            captured.append(cap)
            shapes = out_shape if isinstance(out_shape, (tuple, list)) \
                and not hasattr(out_shape, "shape") else [out_shape]
            outs = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return outs[0] if len(outs) == 1 else tuple(outs)

        return runner

    pl_mod.pallas_call = fake
    try:
        yield
    finally:
        pl_mod.pallas_call = real


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def _out_list(cap: CapturedCall) -> list[tuple[Any, Any]]:
    specs = cap.out_specs if isinstance(cap.out_specs, (tuple, list)) \
        else [cap.out_specs]
    shapes = cap.out_shape if isinstance(cap.out_shape, (tuple, list)) \
        and not hasattr(cap.out_shape, "shape") else [cap.out_shape]
    return list(zip(specs, shapes))


def _kernel_arity(kernel) -> tuple[int, bool, str]:
    """(positional refs, whether it takes ``*refs`` beyond them, name)."""
    f, bound = kernel, set()
    while isinstance(f, functools.partial):
        bound |= set(f.keywords or {})
        f = f.func
    sig = inspect.signature(f)
    n = sum(1 for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            and p.name not in bound)
    variadic = any(p.kind == p.VAR_POSITIONAL
                   for p in sig.parameters.values())
    return n, variadic, getattr(f, "__name__", str(f))


def _check_call(cap: CapturedCall, findings: list[Finding]) -> None:
    where = f"pallas_call in case '{cap.case}'"

    def flag(code: str, msg: str) -> None:
        findings.append(Finding(code, cap.path, cap.line, 0,
                                f"{msg} ({where})"))

    prefetch = cap.operands[:cap.num_scalar_prefetch]
    grid_ops = cap.operands[cap.num_scalar_prefetch:]
    outs = _out_list(cap)

    # RPL303: kernel signature arity vs the grid spec
    n_params, variadic, kname = _kernel_arity(cap.kernel)
    expected = (cap.num_scalar_prefetch + len(cap.in_specs) + len(outs)
                + len(cap.scratch_shapes))
    if n_params > expected or (n_params < expected and not variadic):
        flag("RPL303",
             f"kernel '{kname}' takes {n_params} positional refs but the "
             f"grid spec provides {expected} ({cap.num_scalar_prefetch} "
             f"scalar-prefetch + {len(cap.in_specs)} inputs + {len(outs)} "
             f"outputs + {len(cap.scratch_shapes)} scratch)")
    if len(cap.in_specs) != len(grid_ops):
        flag("RPL303",
             f"{len(grid_ops)} gridded operands passed but "
             f"{len(cap.in_specs)} in_specs declared")

    # RPL304: dtype consistency across array operands and the output
    arrays = [o for o in grid_ops if o.ndim >= 3]
    dtypes = {str(o.dtype) for o in arrays}
    out_dtypes = {str(np.dtype(s.dtype)) for _, s in outs}
    if len(dtypes) > 1:
        flag("RPL304",
             f"array operands disagree on dtype: {sorted(dtypes)}")
    elif dtypes and out_dtypes - dtypes:
        flag("RPL304",
             f"out_shape dtype {sorted(out_dtypes)} != operand dtype "
             f"{sorted(dtypes)}")

    # RPL301 + RPL302 per (spec, shape) pair, inputs then outputs
    pairs = [(f"input {i}", spec, op.shape)
             for i, (spec, op) in enumerate(zip(cap.in_specs, grid_ops))]
    pairs += [(f"output {i}", spec, tuple(s.shape))
              for i, (spec, s) in enumerate(outs)]
    grid_points = list(itertools.product(*(range(g) for g in cap.grid)))
    for label, spec, shape in pairs:
        if spec.block_shape is None:
            continue  # the whole operand (e.g. left in HBM for manual DMA)
        bs = tuple(spec.block_shape)
        if len(bs) != len(shape):
            flag("RPL301",
                 f"{label}: block rank {len(bs)} != operand rank "
                 f"{len(shape)}")
            continue
        for d, (b, s) in enumerate(zip(bs, shape)):
            if b <= 0 or s % b != 0:
                flag("RPL302",
                     f"{label}: block shape {bs} does not tile operand "
                     f"shape {shape} (axis {d}: {s} % {b} != 0)")
                break
        imap = spec.index_map
        if imap is None:
            continue
        bad = 0
        first: tuple | None = None
        for pt in grid_points:
            idx = imap(*pt, *prefetch)
            if not isinstance(idx, tuple):
                idx = (idx,)
            if len(idx) != len(bs):
                flag("RPL301",
                     f"{label}: index map returns {len(idx)} indices for "
                     f"a rank-{len(bs)} block")
                bad = -1
                break
            for b, s, i in zip(bs, shape, (int(v) for v in idx)):
                if i < 0 or i * b + b > s:
                    bad += 1
                    if first is None:
                        first = (pt, tuple(int(v) for v in idx))
                    break
        if bad > 0:
            gp, bi = first
            flag("RPL301",
                 f"{label}: index map leaves operand shape {shape} at "
                 f"{bad}/{len(grid_points)} grid points (first: grid "
                 f"{gp} -> block index {bi}, block shape {bs})")


# ---------------------------------------------------------------------------
# the case registry — mirrors tests/test_kernels.py shapes
# ---------------------------------------------------------------------------

@dataclass
class KernelCase:
    name: str
    thunk: Callable[[], Any]


def _paged_tables(B: int, P: int, ps: int, mp: int):
    """Deterministic page runs per slot: distinct pages off a free list,
    partial last pages, null-page (0) tails — the tests' layout without
    their RNG."""
    pt = np.zeros((B, mp), np.int32)
    free = list(range(1, P))
    lengths = []
    for b in range(B):
        n_pages = min(mp, len(free))
        for i in range(n_pages):
            pt[b, i] = free.pop(0)
        full = n_pages * ps
        lengths.append(max(1, full - (b % ps) - 1) if n_pages else 0)
    return pt, np.asarray(lengths, np.int32)


def default_cases() -> list[KernelCase]:
    from repro.kernels.decode_attention import (
        pallas_decode_attention, pallas_paged_decode_attention)
    from repro.kernels.ragged_attention import pallas_ragged_paged_attention

    cases: list[KernelCase] = []

    def z(shape, dtype=np.float32):
        return np.zeros(shape, dtype)

    # dense decode — tests/test_kernels.py::test_decode_kernel_vs_oracle
    for B, T, Hq, Hkv, D, bk in [(3, 96, 8, 2, 16, 32),
                                 (1, 64, 4, 4, 32, 16),
                                 (2, 128, 16, 8, 8, 64)]:
        def dense(B=B, T=T, Hq=Hq, Hkv=Hkv, D=D, bk=bk):
            lengths = np.arange(1, B + 1) * (T // (B + 1)) + 1
            return pallas_decode_attention(
                z((B, 1, Hq, D)), z((B, T, Hkv, D)), z((B, T, Hkv, D)),
                lengths=lengths, block_kv=bk)
        cases.append(KernelCase(
            f"decode_dense[B{B},T{T},Hq{Hq},Hkv{Hkv},D{D},bk{bk}]", dense))

    # paged decode — ::test_paged_decode_kernel_vs_gather_oracle
    for B, Hq, Hkv, D, P, ps, mp in [(3, 8, 2, 16, 12, 8, 4),
                                     (1, 4, 4, 32, 5, 16, 2),
                                     (2, 16, 8, 8, 9, 4, 8)]:
        def paged(B=B, Hq=Hq, Hkv=Hkv, D=D, P=P, ps=ps, mp=mp):
            pt, lengths = _paged_tables(B, P, ps, mp)
            return pallas_paged_decode_attention(
                z((B, 1, Hq, D)), z((P, Hkv, ps, D)), z((P, Hkv, ps, D)),
                pt, lengths)
        cases.append(KernelCase(
            f"decode_paged[B{B},Hq{Hq},Hkv{Hkv},D{D},P{P},ps{ps},mp{mp}]",
            paged))

    # ragged paged — ::test_ragged_paged_kernel_vs_gather_oracle packings
    seg_lists = [
        [(1, 7), (1, 13), (0, 0), (8, 8), (5, 11)],
        [(1, 5), (1, 9), (1, 16), (1, 1)],
        [(1, 6), (0, 0), (0, 0)],
        [(7, 7), (3, 15)],
    ]
    Hq, Hkv, D, ps, mp, max_q = 4, 2, 16, 4, 6, 8
    for segs in seg_lists:
        def ragged(segs=segs):
            S = len(segs)
            P = 1 + sum(-(-kv // ps) for _, kv in segs) + 1
            pt = np.zeros((S, mp), np.int32)
            free = list(range(1, P))
            q_start, q_len, kv_len = [], [], []
            off = 0
            for ql, kl in segs:
                q_start.append(off)
                q_len.append(ql)
                kv_len.append(kl)
                for i in range(-(-kl // ps)):
                    pt[len(q_start) - 1, i] = free.pop(0)
                off += ql
            T = max(off, 1)
            return pallas_ragged_paged_attention(
                z((T, Hq, D)), z((P, Hkv, ps, D)), z((P, Hkv, ps, D)), pt,
                np.asarray(q_start, np.int32), np.asarray(q_len, np.int32),
                np.asarray(kv_len, np.int32), max_q=max_q)
        cases.append(KernelCase(f"ragged_paged[segs={segs}]", ragged))

    # the multi-page block walk — ::test_ragged_paged_kernel_vs_gather_oracle
    # block cases: 32-token pages, 40 a segment, three blocks of up to 16
    # pages, the last partial; segments end on, past and inside a block
    def blocks():
        segs = [(1, 512), (1, 1025), (0, 0), (8, 520), (1, 100)]
        S, ps_b, mp_b = len(segs), 32, 40
        P = 1 + sum(-(-kv // ps_b) for _, kv in segs)
        pt = np.zeros((S, mp_b), np.int32)
        free = iter(range(1, P))
        for s, (_, kl) in enumerate(segs):
            for i in range(-(-kl // ps_b)):
                pt[s, i] = next(free)
        ql = np.asarray([q for q, _ in segs], np.int32)
        return pallas_ragged_paged_attention(
            z((int(ql.sum()), Hq, D)), z((P, Hkv, ps_b, D)),
            z((P, Hkv, ps_b, D)), pt,
            np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32), ql,
            np.asarray([kv for _, kv in segs], np.int32), max_q=max_q)
    cases.append(KernelCase("ragged_paged[blocks,ps32,mp40]", blocks))

    # speculative verify windows — the PackedSpeculator's decode-segment
    # geometries: K+1-wide verify segments (max_q = 5 at K = 4, one token
    # committed + K drafts, causal within the segment, including a
    # max_seq-capped partial window) and the 2-wide draft catch-up stride.
    # Bounds must hold when every segment is multi-token and reads a
    # ragged kv frontier that ends mid-page.
    spec_layouts = [
        ([(5, 12), (5, 17), (2, 9), (0, 0)], 5),  # verify: K=4, one capped
        ([(2, 8), (1, 5), (2, 21), (2, 2)], 2),   # draft catch-up stride
    ]
    for segs, w in spec_layouts:
        def verify(segs=segs, w=w):
            S = len(segs)
            P = 1 + sum(-(-kv // ps) for _, kv in segs) + 1
            pt = np.zeros((S, mp), np.int32)
            free = list(range(1, P))
            q_start, q_len, kv_len = [], [], []
            for s, (ql, kl) in enumerate(segs):
                q_start.append(s * w)  # fixed verify-window stride
                q_len.append(ql)
                kv_len.append(kl)
                for i in range(-(-kl // ps)):
                    pt[s, i] = free.pop(0)
            return pallas_ragged_paged_attention(
                z((S * w, Hq, D)), z((P, Hkv, ps, D)), z((P, Hkv, ps, D)),
                pt, np.asarray(q_start, np.int32),
                np.asarray(q_len, np.int32), np.asarray(kv_len, np.int32),
                max_q=w)
        cases.append(KernelCase(f"ragged_paged[spec,w{w},segs={segs}]",
                                verify))
    cases.extend(sharded_cases())
    return cases


def sharded_cases() -> list[KernelCase]:
    """Per-shard operand shapes from the mesh-sharded unified step.

    Under ``shard_map`` every worker sees the *local* slice of the paged
    pools — kv heads divided by tp, layers by pp — and runs the very same
    kernels on them with its per-shard page table.  An index map proven
    in bounds for the full shapes is not automatically in bounds for the
    shard (``sh // hkv`` walks a *smaller* hkv), so the registry
    re-checks the kernels at the local geometry the sharded engine
    produces: base Hq=8 / Hkv=4 / D=16 at tp in {2, 4} -> local Hq=4 /
    Hkv=2 and the degenerate-but-legal Hq=2 / Hkv=1 (MHA-per-shard).
    """
    from repro.kernels.decode_attention import pallas_paged_decode_attention
    from repro.kernels.ragged_attention import pallas_ragged_paged_attention

    cases: list[KernelCase] = []

    def z(shape, dtype=np.float32):
        return np.zeros(shape, dtype)

    base_hq, base_hkv, D, ps, mp = 8, 4, 16, 8, 4
    segs = [(1, 7), (5, 13), (0, 0), (1, 20)]
    for tp in (2, 4):
        hq, hkv = base_hq // tp, base_hkv // tp

        def paged(B=3, hq=hq, hkv=hkv, D=D, P=9, ps=ps, mp=mp):
            pt, lengths = _paged_tables(B, P, ps, mp)
            return pallas_paged_decode_attention(
                z((B, 1, hq, D)), z((P, hkv, ps, D)), z((P, hkv, ps, D)),
                pt, lengths)
        cases.append(KernelCase(
            f"decode_paged[tp{tp},Hq{hq},Hkv{hkv},D{D}]", paged))

        def ragged(segs=segs, hq=hq, hkv=hkv):
            S = len(segs)
            P = 1 + sum(-(-kv // ps) for _, kv in segs) + 1
            pt = np.zeros((S, mp), np.int32)
            free = list(range(1, P))
            q_start, q_len, kv_len = [], [], []
            off = 0
            for ql, kl in segs:
                q_start.append(off)
                q_len.append(ql)
                kv_len.append(kl)
                for i in range(-(-kl // ps)):
                    pt[len(q_start) - 1, i] = free.pop(0)
                off += ql
            T = max(off, 1)
            return pallas_ragged_paged_attention(
                z((T, hq, D)), z((P, hkv, ps, D)), z((P, hkv, ps, D)), pt,
                np.asarray(q_start, np.int32), np.asarray(q_len, np.int32),
                np.asarray(kv_len, np.int32), max_q=8)
        cases.append(KernelCase(
            f"ragged_paged[tp{tp},Hq{hq},Hkv{hkv},segs={segs}]", ragged))
    return cases


def check_kernel_bounds(
        cases: list[KernelCase] | None = None) -> list[Finding]:
    """Run every case under the recorder and validate all captured calls."""
    if cases is None:
        cases = default_cases()
    findings: list[Finding] = []
    for case in cases:
        captured: list[CapturedCall] = []
        try:
            with capture_pallas_calls(captured):
                case.thunk()
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                "RPL303", "<case>", 0, 0,
                f"case '{case.name}' failed before/at pallas_call: "
                f"{type(exc).__name__}: {exc}"))
            continue
        for cap in captured:
            cap.case = case.name
            _check_call(cap, findings)
    return findings
