"""Model-level spatial pipelining: transformer layer groups as Kitsune
pipeline stages across mesh devices (inter-chip dataflow, DESIGN.md SS2.2).

Wraps core.queue.spatial_pipeline (ppermute ring queue + GPipe schedule) for
a stack of residual blocks: stage s holds layers [s*L/S, (s+1)*L/S); a
microbatch tile finishes stage s and rides the ICI ring to stage s+1 while
stage s starts the next tile -- operators co-executing across space.

This is the TPU expression of the paper's cudaPipeline: co-residency is a
mesh-axis assignment, queue depth-2 double buffering comes from the
scan-step overlap of compute with the next ppermute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.queue import spatial_pipeline


def stack_stage_params(layer_params, n_stages: int):
    """Regroup per-layer stacked params (leading dim L) into per-stage
    params (leading dim n_stages, each holding L/S layers)."""
    def regroup(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(regroup, layer_params)


def make_pipelined_stack(mesh, layer_fn, n_layers: int, n_stages: int,
                         axis_name: str = "stage"):
    """layer_fn(p, x) -> x applies ONE layer.  Returns
    fn(stage_params, xs) running the depth-n_layers stack as an
    n_stages-deep spatial pipeline over microbatches xs (n_micro, ...)."""
    per_stage = n_layers // n_stages

    def stage_fn(params, x):
        # apply this stage's layer slice sequentially (VMEM-local dataflow)
        def body(x, p):
            return layer_fn(p, x), None
        x, _ = jax.lax.scan(body, x, params)
        return x

    return spatial_pipeline(
        lambda p, x: stage_fn(p, x), n_stages, axis_name)


def run_pipelined(mesh, layer_fn, layer_params, xs, n_stages: int,
                  axis_name: str = "stage"):
    """Convenience wrapper: shard-map the pipelined stack over `axis_name`.

    layer_params: pytree with leading layer dim L; xs: (n_micro, *tile)."""
    n_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
    stage_params = stack_stage_params(layer_params, n_stages)
    pipe = make_pipelined_stack(mesh, layer_fn, n_layers, n_stages, axis_name)
    fn = shard_map(pipe, mesh=mesh, in_specs=(P(axis_name), P()),
                   out_specs=P(), check_vma=False)
    return fn(stage_params, xs)
