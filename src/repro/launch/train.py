"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --steps 100 --batch 8 --seq 64 [--devices 8 --tp 2]

Runs the fault-tolerant trainer on the chosen architecture (reduced config
by default on CPU; the full config is for real fleets), with checkpointing,
straggler monitoring and deterministic resume.  ``--devices N`` under
``JAX_PLATFORMS=cpu`` fakes an N-chip host for a sharded run (set before
jax initializes, hence the call at the top); on an accelerator the real
devices are used.
"""

import argparse
import sys

from .runtime import force_host_devices

force_host_devices(sys.argv)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import registry  # noqa: E402
from ..data.pipeline import DataConfig  # noqa: E402
from ..models import build_model  # noqa: E402
from ..training.fault import run_with_restarts  # noqa: E402
from ..training.optimizer import AdamWConfig  # noqa: E402
from ..training.train_loop import TrainConfig, Trainer  # noqa: E402
from .mesh import make_mesh  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true", default=True)
    args = ap.parse_args()

    spec = (registry.get_reduced(args.arch) if args.reduced
            else registry.get_spec(args.arch))
    mesh = None
    policy = None
    if args.devices and args.devices > 1:
        mesh = make_mesh((args.devices // args.tp, args.tp),
                         ("data", "model"))
        policy = "train_2d"
        print(f"mesh: {mesh}")
    model = build_model(spec, mesh=mesh, policy=policy,
                        param_dtype=jnp.float32, compute_dtype=jnp.float32)
    data_cfg = DataConfig(vocab=spec.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    cfg = TrainConfig(total_steps=args.steps,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_dir=args.checkpoint_dir,
                      optimizer=AdamWConfig(lr=args.lr, warmup_steps=10,
                                            total_steps=args.steps))

    def make(attempt):
        if attempt:
            print(f"[supervisor] restart #{attempt}")
        return Trainer(model, data_cfg, cfg, rng=jax.random.key(0),
                       mesh=mesh)

    def cb(step, loss):
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {loss:.4f}")

    tr = make(0)
    start = tr.resume() if args.resume else 0
    if start:
        print(f"resumed from step {start}")
    tr.run(start, args.steps, callback=cb)
    n_straggle = len(tr.monitor.flagged)
    print(f"done: {len(tr.history)} steps this run, "
          f"{n_straggle} straggler events, final loss "
          f"{tr.history[-1]['loss']:.4f}" if tr.history else "done (resumed)")


if __name__ == "__main__":
    main()
