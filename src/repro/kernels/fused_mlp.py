"""Dataflow-fused MLP Pallas kernel -- the paper's Fig 2(a) pattern on TPU.

    Y = act(X @ W1) @ W2            (gelu / relu)
    Y = (silu(X @ Wg) * (X @ Wu)) @ Wd   (SwiGLU)

Kitsune's point: under BSP (and under vertical fusion once the hidden dim
exceeds on-chip capacity) the (M, H) intermediate round-trips through
DRAM/HBM.  Here the hidden dimension is *spatially split* over the Pallas
grid: each grid step materializes only a (block_m, block_h) hidden tile in
VMEM -- the on-chip queue payload -- consumes it immediately into the second
GEMM, and accumulates into a VMEM f32 scratch.  The (M, H) tensor never
exists in HBM.  MXU (two GEMMs) and VPU (activation) work interleave inside
one program, which is the TPU realization of the paper's heterogeneous-CTA
co-execution (DESIGN.md SS2 assumption 2).

HBM traffic: read X, W1, W2 (, Wu) once; write Y once.  BSP traffic adds
2 * M*H bytes; for a transformer FFN that is the dominant term.

The backward pass implements Fig 2(c)'s multicast: one recomputed hidden/
act-grad tile feeds BOTH the dX GEMM and the dW GEMMs (split into two
kernels so each output's accumulation order is grid-consecutive).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# d/dx act(x): ONE derivative table shared with the jnp oracles (ref.py)
from .ref import _DACTS, _dgelu

_ACTS = {
    "gelu": jax.nn.gelu,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "silu": jax.nn.silu,
    "identity": lambda x: x,
}


# Scoped-VMEM budget Mosaic assumes when a kernel sets no limit (v5e).
DEFAULT_SCOPED_VMEM = 16 << 20


def block_h_for(hdim: int, cap: int) -> int:
    """Hidden-dim tile: the largest multiple of 128 (the TPU lane width)
    that divides `hdim` and is <= `cap`; the whole dim when no multiple of
    128 divides it (small CPU-test widths)."""
    for bh in range(cap - cap % 128, 0, -128):
        if hdim % bh == 0:
            return bh
    return hdim


def tile_candidates(m: int, hdim: int) -> list[dict]:
    """Autotune grid for the fused-MLP kernels (fwd and bwd share tiles --
    ops._blocks is the single tiling rule): the distinct (block_m, block_h)
    tiles that rule yields for a sweep of caps, so every candidate is one
    the TPU compiler accepts.  The historical 128/512 default is among the
    caps.  The autotuner (kernels/autotune.py) times each at first-build."""
    bms = sorted({min(bm, m) for bm in (32, 64, 128, 256)})
    bhs = sorted({block_h_for(hdim, cap) for cap in (128, 256, 512, 1024)})
    return [{"block_m": bm, "block_h": bh} for bm in bms for bh in bhs]


def _compiler_params(block_bytes: int, scratch_bytes: int):
    """Raise the kernel's scoped-VMEM limit when its working set (blocks
    double-buffered by the pipeline, plus scratch, plus headroom for the
    f32 temporaries of the tile math) exceeds the default."""
    need = (2 * block_bytes + scratch_bytes) * 3 // 2
    if need <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, 100 << 20))


def _nbytes(*blocks) -> int:
    """Bytes of (shape, dtype) blocks."""
    total = 0
    for shape, dtype in blocks:
        n = jnp.dtype(dtype).itemsize
        for d in shape:
            n *= d
        total += n
    return total


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w1_ref, w2_ref, o_ref, acc_ref, *, act: str, n_h: int):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the queue payload: (block_m, block_h) hidden tile, VMEM-resident
    t = _ACTS[act](jnp.dot(x_ref[...], w1_ref[...],
                           preferred_element_type=jnp.float32))
    acc_ref[...] += jnp.dot(t.astype(x_ref.dtype), w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(h == n_h - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fwd_kernel_swiglu(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                       act: str, n_h: int):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    t = _ACTS[act](g) * u
    acc_ref[...] += jnp.dot(t.astype(x.dtype), wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(h == n_h - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fused_mlp_fwd(x: jax.Array, w1: jax.Array, w2: jax.Array,
                  *, act: str = "gelu", block_m: int = 128,
                  block_h: int = 512, interpret: bool = False) -> jax.Array:
    """act(x @ w1) @ w2 with the hidden dim streamed through VMEM."""
    m, d_in = x.shape
    _, hdim = w1.shape
    d_out = w2.shape[1]
    assert m % block_m == 0 and hdim % block_h == 0, (m, hdim, block_m, block_h)
    n_m, n_h = m // block_m, hdim // block_h
    dt = x.dtype
    params = _compiler_params(
        _nbytes(((block_m, d_in), dt), ((d_in, block_h), w1.dtype),
                ((block_h, d_out), w2.dtype), ((block_m, d_out), dt)),
        _nbytes(((block_m, d_out), jnp.float32),
                ((block_m, block_h), jnp.float32)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act, n_h=n_h),
        grid=(n_m, n_h),
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda i, h: (h, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d_out), lambda i, h: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d_out), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(x, w1, w2)


def fused_mlp_swiglu_fwd(x: jax.Array, wg: jax.Array, wu: jax.Array,
                         wd: jax.Array, *, act: str = "silu",
                         block_m: int = 128, block_h: int = 512,
                         interpret: bool = False) -> jax.Array:
    """(act(x @ wg) * (x @ wu)) @ wd -- SwiGLU with act=silu; the gate
    activation is a parameter so plain gate*up dual-GEMM blocks (act=
    identity, the builder-graph form) lower onto the same kernel."""
    m, d_in = x.shape
    _, hdim = wg.shape
    d_out = wd.shape[1]
    assert m % block_m == 0 and hdim % block_h == 0, (m, hdim, block_m, block_h)
    n_m, n_h = m // block_m, hdim // block_h
    dt = x.dtype
    params = _compiler_params(
        _nbytes(((block_m, d_in), dt), ((d_in, block_h), wg.dtype),
                ((d_in, block_h), wu.dtype), ((block_h, d_out), wd.dtype),
                ((block_m, d_out), dt)),
        _nbytes(((block_m, d_out), jnp.float32),
                ((3 * block_m, block_h), jnp.float32)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel_swiglu, act=act, n_h=n_h),
        grid=(n_m, n_h),
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda i, h: (h, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d_out), lambda i, h: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d_out), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(x, wg, wu, wd)


# ---------------------------------------------------------------------------
# backward (Fig 2c multicast): dX kernel + dW kernel
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(x_ref, w1_ref, w2_ref, dy_ref, dx_ref, acc_ref,
                   *, act: str, n_h: int):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # recompute the hidden tile (queue recompute beats HBM spill)
    pre = jnp.dot(x_ref[...], w1_ref[...], preferred_element_type=jnp.float32)
    dt = jnp.dot(dy_ref[...], w2_ref[...].T, preferred_element_type=jnp.float32)
    da = dt * _DACTS[act](pre)
    acc_ref[...] += jnp.dot(da.astype(x_ref.dtype), w1_ref[...].T,
                            preferred_element_type=jnp.float32)

    @pl.when(h == n_h - 1)
    def _done():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w1_ref, w2_ref, dy_ref, dw1_ref, dw2_ref,
                   a1_ref, a2_ref, *, act: str, n_m: int):
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        a1_ref[...] = jnp.zeros_like(a1_ref)
        a2_ref[...] = jnp.zeros_like(a2_ref)

    x = x_ref[...]
    pre = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    t = _ACTS[act](pre)
    dy = dy_ref[...]
    # multicast: ONE staged tile pair (t, da) feeds both weight-grad GEMMs
    a2_ref[...] += jnp.dot(t.astype(x.dtype).T, dy,
                           preferred_element_type=jnp.float32)
    dt = jnp.dot(dy, w2_ref[...].T, preferred_element_type=jnp.float32)
    da = dt * _DACTS[act](pre)
    a1_ref[...] += jnp.dot(x.T, da.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @pl.when(m == n_m - 1)
    def _done():
        dw1_ref[...] = a1_ref[...].astype(dw1_ref.dtype)
        dw2_ref[...] = a2_ref[...].astype(dw2_ref.dtype)


def _bwd_dx_kernel_swiglu(x_ref, wg_ref, wu_ref, wd_ref, dy_ref, dx_ref,
                          acc_ref, *, act: str, n_h: int):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # recompute the gate/up tiles (queue recompute beats HBM spill)
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    dt = jnp.dot(dy_ref[...], wd_ref[...].T, preferred_element_type=jnp.float32)
    dg = dt * u * _DACTS[act](g)
    du = dt * _ACTS[act](g)
    acc_ref[...] += jnp.dot(dg.astype(x.dtype), wg_ref[...].T,
                            preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(du.astype(x.dtype), wu_ref[...].T,
                            preferred_element_type=jnp.float32)

    @pl.when(h == n_h - 1)
    def _done():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _bwd_dw_kernel_swiglu(x_ref, wg_ref, wu_ref, wd_ref, dy_ref,
                          dwg_ref, dwu_ref, dwd_ref, ag_ref, au_ref, ad_ref,
                          *, act: str, n_m: int):
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        ag_ref[...] = jnp.zeros_like(ag_ref)
        au_ref[...] = jnp.zeros_like(au_ref)
        ad_ref[...] = jnp.zeros_like(ad_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    sg = _ACTS[act](g)
    t = sg * u
    dy = dy_ref[...]
    # multicast: ONE staged tile set (t, dg, du) feeds all three weight-grad
    # GEMMs -- the Fig 2(c) pattern, gated variant
    ad_ref[...] += jnp.dot(t.astype(x.dtype).T, dy,
                           preferred_element_type=jnp.float32)
    dt = jnp.dot(dy, wd_ref[...].T, preferred_element_type=jnp.float32)
    dg = dt * u * _DACTS[act](g)
    du = dt * sg
    ag_ref[...] += jnp.dot(x.T, dg.astype(x.dtype),
                           preferred_element_type=jnp.float32)
    au_ref[...] += jnp.dot(x.T, du.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @pl.when(m == n_m - 1)
    def _done():
        dwg_ref[...] = ag_ref[...].astype(dwg_ref.dtype)
        dwu_ref[...] = au_ref[...].astype(dwu_ref.dtype)
        dwd_ref[...] = ad_ref[...].astype(dwd_ref.dtype)


def fused_mlp_swiglu_bwd(x, wg, wu, wd, dy, *, act: str = "silu",
                         block_m: int = 128, block_h: int = 512,
                         interpret: bool = False):
    """Backward of (act(x@wg) * (x@wu)) @ wd -- the gated variant of the
    Fig 2(c) multicast: recomputed gate/up tiles feed the dX GEMM pair and
    all three weight-grad GEMMs without the (M, H) tensors touching HBM."""
    m, d_in = x.shape
    _, hdim = wg.shape
    d_out = wd.shape[1]
    assert m % block_m == 0 and hdim % block_h == 0, (m, hdim, block_m, block_h)
    n_m, n_h = m // block_m, hdim // block_h
    dt, f32 = x.dtype, jnp.float32
    in_bytes = _nbytes(((block_m, d_in), dt), ((d_in, block_h), wg.dtype),
                       ((d_in, block_h), wu.dtype),
                       ((block_h, d_out), wd.dtype), ((block_m, d_out), dt))
    tile_tmp = _nbytes(((6 * block_m, block_h), f32))
    dx_params = _compiler_params(
        in_bytes + _nbytes(((block_m, d_in), dt)),
        _nbytes(((block_m, d_in), f32)) + tile_tmp)
    dw_params = _compiler_params(
        in_bytes + _nbytes(((2 * d_in + d_out, block_h), f32)),
        _nbytes(((2 * d_in + d_out, block_h), f32)) + tile_tmp)
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel_swiglu, act=act, n_h=n_h),
        grid=(n_m, n_h),
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda i, h: (h, 0)),
            pl.BlockSpec((block_m, d_out), lambda i, h: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d_in), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d_in), jnp.float32)],
        compiler_params=dx_params,
        interpret=interpret,
    )(x, wg, wu, wd, dy)
    dwg, dwu, dwd = pl.pallas_call(
        functools.partial(_bwd_dw_kernel_swiglu, act=act, n_m=n_m),
        grid=(n_h, n_m),  # m innermost: dW accumulation is grid-consecutive
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda h, i: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda h, i: (h, 0)),
            pl.BlockSpec((block_m, d_out), lambda h, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda h, i: (h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_in, hdim), jnp.float32),
            jax.ShapeDtypeStruct((d_in, hdim), jnp.float32),
            jax.ShapeDtypeStruct((hdim, d_out), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_in, block_h), jnp.float32),
                        pltpu.VMEM((d_in, block_h), jnp.float32),
                        pltpu.VMEM((block_h, d_out), jnp.float32)],
        compiler_params=dw_params,
        interpret=interpret,
    )(x, wg, wu, wd, dy)
    return (dx, dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype))


def fused_mlp_bwd(x, w1, w2, dy, *, act: str = "gelu", block_m: int = 128,
                  block_h: int = 512, interpret: bool = False):
    m, d_in = x.shape
    _, hdim = w1.shape
    d_out = w2.shape[1]
    assert m % block_m == 0 and hdim % block_h == 0, (m, hdim, block_m, block_h)
    n_m, n_h = m // block_m, hdim // block_h
    dt, f32 = x.dtype, jnp.float32
    in_bytes = _nbytes(((block_m, d_in), dt), ((d_in, block_h), w1.dtype),
                       ((block_h, d_out), w2.dtype), ((block_m, d_out), dt))
    tile_tmp = _nbytes(((4 * block_m, block_h), f32))
    dx_params = _compiler_params(
        in_bytes + _nbytes(((block_m, d_in), dt)),
        _nbytes(((block_m, d_in), f32)) + tile_tmp)
    dw_params = _compiler_params(
        in_bytes + _nbytes(((d_in + d_out, block_h), f32)),
        _nbytes(((d_in + d_out, block_h), f32)) + tile_tmp)
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, act=act, n_h=n_h),
        grid=(n_m, n_h),
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda i, h: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda i, h: (h, 0)),
            pl.BlockSpec((block_m, d_out), lambda i, h: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d_in), lambda i, h: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d_in), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d_in), jnp.float32)],
        compiler_params=dx_params,
        interpret=interpret,
    )(x, w1, w2, dy)
    dw1, dw2 = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, act=act, n_m=n_m),
        grid=(n_h, n_m),  # m innermost: dW accumulation is grid-consecutive
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda h, i: (i, 0)),
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda h, i: (h, 0)),
            pl.BlockSpec((block_m, d_out), lambda h, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_in, block_h), lambda h, i: (0, h)),
            pl.BlockSpec((block_h, d_out), lambda h, i: (h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_in, hdim), jnp.float32),
            jax.ShapeDtypeStruct((hdim, d_out), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_in, block_h), jnp.float32),
                        pltpu.VMEM((block_h, d_out), jnp.float32)],
        compiler_params=dw_params,
        interpret=interpret,
    )(x, w1, w2, dy)
    return dx, dw1.astype(w1.dtype), dw2.astype(w2.dtype)
