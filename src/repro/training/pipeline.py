"""Pipeline parallelism over the ``pod`` mesh axis (GPipe schedule).

The multi-pod mesh's outer axis crosses the slow inter-pod DCN; its two
natural uses are data parallelism (the default; gradients cross pods once
per step) and pipeline parallelism (activations cross pods once per
microbatch — much smaller payloads, the better choice when the DP gradient
all-reduce dominates the collective term; see EXPERIMENTS.md §Perf).

Implementation: ``shard_map`` over the pod axis.  Layer super-block stacks
are sharded so each pod holds ``n_layers / n_pods`` consecutive layers; the
forward runs a GPipe loop of ``n_micro + n_pods - 1`` ticks, rotating
microbatch activations between neighbor pods with ``lax.ppermute``.  The
bubble fraction is the standard (p-1)/(m+p-1).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



@dataclass(frozen=True)
class PipelineConfig:
    n_micro: int = 4
    axis: str = "pod"


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(layer_fn, n_stages: int, cfg: PipelineConfig,
                     params_stacked, x_micro):
    """Run inside shard_map over ``cfg.axis``.

    layer_fn(params_slice, x) -> x : applies this stage's layers.
    params_stacked: this stage's layer stack (already sharded by stage).
    x_micro: (n_micro, mb, S, D) — microbatches, same on every stage
             (stage 0 uses them; others ignore their copy).
    Returns (n_micro, mb, S, D) final-stage outputs (valid on the last
    stage; other stages hold zeros).
    """
    axis = cfg.axis
    stage = jax.lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    mb_shape = x_micro.shape[1:]

    def tick(carry, t):
        buf, outputs = carry  # buf: activation entering this stage
        # stage 0 feeds microbatch t (when valid)
        feed = jnp.where(t < n_micro,
                         x_micro[jnp.minimum(t, n_micro - 1)],
                         jnp.zeros(mb_shape, x_micro.dtype))
        inp = jnp.where(stage == 0, feed, buf)
        out = layer_fn(params_stacked, inp)
        # last stage banks microbatch (t - (n_stages-1)) when valid
        mb_idx = t - (n_stages - 1)
        valid = (mb_idx >= 0) & (mb_idx < n_micro)
        outputs = jax.lax.cond(
            valid & (stage == n_stages - 1),
            lambda o: o.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(out),
            lambda o: o, outputs)
        # rotate activations forward one stage
        nxt = jax.lax.ppermute(
            out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
        return (nxt, outputs), None

    buf0 = jnp.zeros(mb_shape, x_micro.dtype)
    outs0 = jnp.zeros_like(x_micro)
    (_, outputs), _ = jax.lax.scan(tick, (buf0, outs0),
                                   jnp.arange(n_ticks))
    # broadcast final outputs from the last stage to all pods
    outputs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, 0.0), axis)
    return outputs


def make_pipelined_fn(layer_fn, mesh, n_stages: int, params_example,
                      cfg: PipelineConfig = PipelineConfig()):
    """Wrap a stage function into a pod-pipelined callable.

    ``params_example``: pytree whose leaves have a leading layer dimension
    (n_stages * layers_per_stage); it is sharded on the pod axis so each pod
    holds its stage's slice.  x: (n_micro, mb, S, D) replicated.
    """
    body = functools.partial(pipeline_forward, layer_fn, n_stages, cfg)
    param_specs = jax.tree.map(lambda _: P(cfg.axis), params_example)
    return jax.shard_map(body, mesh=mesh, in_specs=(param_specs, P()),
                         out_specs=P(), check_vma=False)
