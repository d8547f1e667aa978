"""Kitsune queue primitives, TPU edition (paper SS4.1).

The paper's queue is an L2-pinned, double-buffered ring with atomic
acquire/release.  TPUs have no chip-global L2 nor programmer-visible global
atomics, so the primitive splits into two levels (DESIGN.md SS2, assumption 1):

  * intra-chip ("vmem"): tiles hand off between fused pipeline stages through
    VMEM double-buffering.  Pallas's BlockSpec grid pipeline + DMA semaphores
    *are* the acquire/release protocol in hardware; kernels/ implements the
    compute side.  Here we model its bandwidth/overhead for the Fig-5
    reproduction benchmark.

  * inter-chip ("ici"): a ring queue across mesh devices built on
    jax.lax.ppermute inside shard_map -- used by the spatial device pipeline
    (the mesh-level analogue of CTAs on disjoint SM sets).

`spatial_pipeline` is the GPipe-style schedule: microbatch tiles stream
through the stage ring; steady-state has every stage computing concurrently,
which is precisely Kitsune's "operators co-execute across space".
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


# ---------------------------------------------------------------------------
# Analytic queue-performance model (reproduces the shape of paper Fig 5)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QueueLevel:
    name: str
    raw_bw: float        # B/s of the transport (VMEM or ICI)
    sync_overhead_s: float  # fixed acquire+release cost per payload
    capacity: float      # bytes before the queue spills to the next level
    spill_bw: float      # bandwidth once capacity is exceeded (HBM)


# v5e: VMEM-level queues (DMA semaphore sync ~ O(100ns)); ICI ring queues.
VMEM_QUEUE = QueueLevel("vmem", 18e12, 150e-9, 128 * 2**20, 819e9)
ICI_QUEUE = QueueLevel("ici", 4 * 50e9, 1.0e-6, 128 * 2**20, 819e9)
# A100 L2 queue constants from the paper (SS4.1): atomics sync, 40MB L2,
# spill to HBM at 1.5TB/s.
L2_QUEUE_A100 = QueueLevel("l2-a100", 4.7e12, 400e-9, 40e6, 1.555e12)


def queue_bandwidth(level: QueueLevel, payload_bytes: float,
                    n_queues: int = 1, sync: bool = True) -> float:
    """Effective per-queue bandwidth for a payload size (Fig 5 analogue).

    time/payload = payload/raw_bw + sync_overhead; beyond capacity the
    transport degrades to spill bandwidth (the paper's >256KB L2 overflow).
    """
    total = payload_bytes * n_queues
    bw = level.raw_bw if total * 2 <= level.capacity else level.spill_bw
    per_queue_bw = bw / n_queues
    t = payload_bytes / per_queue_bw + (level.sync_overhead_s if sync else 0.0)
    return payload_bytes / t


# ---------------------------------------------------------------------------
# Inter-chip ring queue + spatial device pipeline
# ---------------------------------------------------------------------------

def ring_spec(axis_name: str, n: int, reverse: bool = False):
    if reverse:
        return [((i + 1) % n, i) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def ring_push(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """One queue hop: every stage sends its tile to the next stage."""
    return lax.ppermute(x, axis_name, ring_spec(axis_name, n))


def spatial_pipeline(stage_fn, n_stages: int, axis_name: str = "stage"):
    """Build a shard_map-able pipelined apply.

    stage_fn(params_slice, x) -> y, with uniform x/y shapes across stages
    (residual-stream pipelining).  Returns fn(params_stacked, xs) where
    params_stacked has a leading stage axis and xs is (n_micro, *tile).

    Schedule: T = n_micro + n_stages - 1 ticks.  Each tick: every device
    computes its stage on its current tile, then the ring queue advances
    (ppermute) -- compute and communication of successive tiles overlap in
    steady state, the dataflow execution model of the paper's SS4.
    """

    def pipelined(params, xs):
        stage = lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        tile_shape = xs.shape[1:]
        total = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(stage == 0, xs[idx], buf)
            y = stage_fn(jax.tree.map(lambda p: p[0], params), inp)
            # emit: the last stage finishes microbatch m = t - (n_stages-1)
            m = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, m >= 0)
            outs = lax.cond(
                emit,
                lambda o: lax.dynamic_update_slice(
                    o, y[None], (jnp.clip(m, 0, n_micro - 1),) + (0,) * len(tile_shape)),
                lambda o: o, outs)
            nxt = ring_push(y, axis_name, n_stages)
            return (nxt, outs), None

        buf0 = jnp.zeros(tile_shape, xs.dtype)
        outs0 = jnp.zeros((n_micro,) + tile_shape, xs.dtype)
        (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(total))
        # outs is populated only on the last stage; broadcast it around the
        # ring so every shard returns the same value (psum over one-hot).
        onehot = (stage == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * onehot, axis_name)

    return pipelined


def make_spatial_pipeline(mesh, stage_fn, n_stages: int, axis_name: str = "stage"):
    """shard_map-wrapped spatial pipeline over `axis_name` of `mesh`."""
    fn = spatial_pipeline(stage_fn, n_stages, axis_name)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis_name), P()),   # params stage-sharded, xs replicated
        out_specs=P(),
        check_vma=False,
    )
