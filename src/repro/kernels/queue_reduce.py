"""Queue-based parallel reduction kernel (paper Fig 2(b), Algorithm 1's
SplitReduction 'final' stage).

BSP reductions over the batch dimension (gradient reductions in backprop)
leave most compute idle: a handful of CTAs walk all the data.  Kitsune splits
the reduction into a spatial fan-in whose partials flow through queues into a
combining stage.  On TPU the fan-in partials arrive either from the Pallas
grid (this kernel: sequential grid steps accumulate tiles through a VMEM
scratch accumulator -- each grid step is one queue pop) or from mesh shards
(lax.psum / reduce_scatter trees, see core/queue.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMBINE = {
    "sum": lambda a, b: a + b,
    "max": jnp.maximum,
    "min": jnp.minimum,
}
_INIT = {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}


def tile_candidates(rows: int) -> list[dict]:
    """Autotune grid for queue_reduce's row tile: divisors of `rows`, with
    the historical fallback rule (128, else 1) always present."""
    brs = [br for br in (8, 32, 128) if rows % br == 0]
    default_br = min(128, rows)
    if rows % default_br:
        default_br = 1
    cands = [{"block_r": br} for br in brs]
    if {"block_r": default_br} not in cands:
        cands.append({"block_r": default_br})
    return cands


def _reduce_kernel(x_ref, o_ref, acc_ref, *, op: str, n: int):
    i = pl.program_id(1)  # reduction step: innermost, so accumulation over
    # the queue is consecutive for each output block

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _INIT[op])

    acc_ref[...] = _COMBINE[op](acc_ref[...], x_ref[0].astype(jnp.float32))

    @pl.when(i == n - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def queue_reduce(x: jax.Array, *, op: str = "sum", block_rows: int = 128,
                 interpret: bool = False) -> jax.Array:
    """Reduce (N, R, C) -> (R, C) over axis 0 through a VMEM accumulator.

    Each grid step consumes one (R-tile, C) payload from the queue and folds
    it into the accumulator; only the final result is written to HBM (BSP
    writes/reads log-tree intermediates)."""
    assert x.ndim == 3, "reshape to (N, rows, cols) first"
    n, r, c = x.shape
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    n_r = r // block_rows
    return pl.pallas_call(
        functools.partial(_reduce_kernel, op=op, n=n),
        grid=(n_r, n),
        in_specs=[pl.BlockSpec((1, block_rows, c), lambda j, i: (i, j, 0))],
        out_specs=pl.BlockSpec((block_rows, c), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, c), jnp.float32)],
        name="queue_reduce",
        interpret=interpret,
    )(x)
