"""Serving launcher: the continuous-batching engine as a CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-moe-16b \
        --requests 8 --max-new 16 [--devices 4 --tp 2]

Reduced configs (full configs are sized for real pods).  ``--devices N``
gives the CPU N virtual devices under ``JAX_PLATFORMS=cpu``; on an
accelerator the real devices are used.  Prints
per-request outputs + engine throughput; ``--n-spec K`` serves through
the unified engine with batched speculative decoding (self-draft: the
target verifies its own proposals, so greedy outputs are unchanged and
the acceptance counters exercise the full path).
"""

import argparse
import os
import sys

from .runtime import force_host_devices

force_host_devices(sys.argv)

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ..configs import registry  # noqa: E402
from ..models import build_model  # noqa: E402
from ..serving import EngineConfig, Request, ServeEngine  # noqa: E402
from ..serving.sampling import SamplingConfig  # noqa: E402
from .mesh import make_mesh  # noqa: E402
from .runtime import use_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--n-spec", type=int, default=0,
                    help="draft window K for batched speculative decoding "
                         "(self-draft; implies the unified paged engine)")
    args = ap.parse_args()
    use_compile_cache(os.path.join(os.path.dirname(__file__), "..", "..",
                                   ".."))

    spec = registry.get_reduced(args.arch)
    if not spec.decoder:
        raise SystemExit(f"{args.arch} is encoder-only")
    mesh = None
    if args.devices and args.devices > 1:
        mesh = make_mesh((args.devices // args.tp, args.tp),
                         ("data", "model"))
    model = build_model(spec, mesh=mesh, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(0, spec.vocab,
                                         size=rng.integers(4, 24))],
                    max_new_tokens=args.max_new,
                    sampling=SamplingConfig(temperature=args.temperature,
                                            top_k=40))
            for _ in range(args.requests)]
    if args.n_spec:
        if mesh is not None:
            raise SystemExit("--n-spec is single-device (the fused "
                             "draft/verify step is not sharded)")
        cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                           chunk_size=args.chunk, cache_layout="paged",
                           unified=True, n_spec=args.n_spec)
        eng = ServeEngine(model, params, cfg, rng=jax.random.key(0),
                          draft_model=model, draft_params=params)
    else:
        eng = ServeEngine(model, params,
                          EngineConfig(max_slots=args.slots,
                                       max_seq=args.max_seq,
                                       chunk_size=args.chunk))
    t0 = time.time()
    if mesh is not None:
        with mesh:
            eng.serve(reqs)
    else:
        eng.serve(reqs)
    dt = time.time() - t0
    toks = sum(len(r.output) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: {len(r.prompt)} tok prompt -> "
              f"{r.output[:10]}{'...' if len(r.output) > 10 else ''}")
    print(f"\n{len(reqs)} requests, {toks} tokens, {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, {eng.steps} engine steps)")
    if args.n_spec:
        m = eng.metrics
        print(f"speculative: acceptance {m.spec_acceptance_rate:.2f}, "
              f"{m.spec_tokens_per_round:.2f} tokens/window over "
              f"{m.spec_slot_rounds} windows")


if __name__ == "__main__":
    main()
