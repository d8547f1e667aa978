"""Jit'd public wrappers around the Pallas kernels.

`use_pallas` selects the dataflow kernels (compiled on TPU; `interpret=True`
on CPU for tests); otherwise the ref.py XLA path runs -- models call these so
the whole framework switches implementation with one config flag.  A Pallas
config must state `interpret`: `repro.core.lower.kernel_config()` derives it
from the attached platform.

`fused_mlp` carries a custom_vjp whose backward is itself a dataflow kernel
pair (Fig 2(c) multicast -- see fused_mlp.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import combine_partials, flash_attention, flash_decode
from .paged_attention import paged_flash_decode
from .fused_mlp import (block_h_for, fused_mlp_bwd, fused_mlp_fwd,
                        fused_mlp_swiglu_bwd, fused_mlp_swiglu_fwd)
from .queue_reduce import queue_reduce


@dataclass(frozen=True)
class KernelConfig:
    use_pallas: bool = False
    interpret: bool | None = None   # Pallas interpret mode (CPU tests only)
    block_m: int = 128
    block_h: int = 512
    block_q: int = 128
    block_k: int = 128
    block_s: int = 256          # flash_decode split-K chunk
    block_r: int = 128          # queue_reduce row tile
    autotune: bool = False      # search tile_candidates grids at lower time

    def __post_init__(self):
        if self.use_pallas and self.interpret is None:
            raise ValueError(
                "KernelConfig(use_pallas=True) must state interpret=; "
                "repro.core.lower.kernel_config() picks it for the platform")
        if self.interpret and jax.default_backend() == "tpu":
            raise ValueError("Pallas interpret mode is for CPU tests; on a "
                             "TPU the kernels run compiled")


def _pad_to(x: jax.Array, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    padw = [(0, 0)] * x.ndim
    padw[axis] = (0, pad)
    return jnp.pad(x, padw), pad


def _blocks(m: int, hdim: int, cfg: KernelConfig) -> tuple[int, int]:
    """The ONE tiling rule for every fused-MLP wrapper, forward and
    backward -- the two directions must always pick the same tiles for the
    same shapes.  Only tiles the TPU compiler accepts: block_m is the whole
    row dim when it fits under the cap, else the cap (a multiple of 8; the
    wrappers pad rows up to it); block_h via `fused_mlp.block_h_for`."""
    return min(cfg.block_m, m), block_h_for(hdim, cfg.block_h)


# ---------------------------------------------------------------------------
# fused MLP with dataflow backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_mlp(x, w1, w2, _dummy, act: str, cfg: KernelConfig):
    return _fused_mlp_fwd_impl(x, w1, w2, act, cfg)


def _fused_mlp_fwd_impl(x, w1, w2, act, cfg):
    m, d_in = x.shape
    bm, bh = _blocks(m, w1.shape[1], cfg)
    xp, pad = _pad_to(x, 0, bm)
    y = fused_mlp_fwd(xp, w1, w2, act=act, block_m=bm, block_h=bh,
                      interpret=cfg.interpret)
    return y[:m] if pad else y


def _fwd(x, w1, w2, _dummy, act, cfg):
    return _fused_mlp(x, w1, w2, _dummy, act, cfg), (x, w1, w2)


def _bwd(act, cfg, res, dy):
    x, w1, w2 = res
    m = x.shape[0]
    bm, bh = _blocks(m, w1.shape[1], cfg)
    xp, pad = _pad_to(x, 0, bm)
    dyp, _ = _pad_to(dy, 0, bm)
    dx, dw1, dw2 = fused_mlp_bwd(xp, w1, w2, dyp, act=act, block_m=bm,
                                 block_h=bh, interpret=cfg.interpret)
    return (dx[:m] if pad else dx), dw1, dw2, None


_fused_mlp.defvjp(_fwd, _bwd)


def mlp(x: jax.Array, w1: jax.Array, w2: jax.Array, *, act: str = "gelu",
        cfg: KernelConfig = KernelConfig()) -> jax.Array:
    """act(x @ w1) @ w2; x may have leading batch dims."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if cfg.use_pallas:
        y = _fused_mlp(x2, w1, w2, None, act, cfg)
    else:
        y = ref.mlp_ref(x2, w1, w2, act)
    return y.reshape(*lead, w2.shape[1])


def mlp_swiglu(x: jax.Array, wg, wu, wd, *, act: str = "silu",
               cfg: KernelConfig = KernelConfig()):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if cfg.use_pallas:
        m = x2.shape[0]
        bm, bh = _blocks(m, wg.shape[1], cfg)
        x2p, pad = _pad_to(x2, 0, bm)
        y = fused_mlp_swiglu_fwd(x2p, wg, wu, wd, act=act, block_m=bm,
                                 block_h=bh, interpret=cfg.interpret)
        y = y[:m] if pad else y
    else:
        y = ref.mlp_swiglu_ref(x2, wg, wu, wd, act=act)
    return y.reshape(*lead, wd.shape[1])


def mlp_bwd(x: jax.Array, w1: jax.Array, w2: jax.Array, dy: jax.Array, *,
            act: str = "gelu", cfg: KernelConfig = KernelConfig()):
    """(dx, dw1, dw2) of act(x @ w1) @ w2; x/dy may have leading batch dims.

    The executable form of the Fig 2(c) multicast: with `use_pallas` the
    recomputed hidden tile feeds the dX and dW GEMMs inside the
    fused_mlp_bwd kernels; otherwise the jnp oracle (same math) runs."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    if cfg.use_pallas:
        m = x2.shape[0]
        bm, bh = _blocks(m, w1.shape[1], cfg)
        xp, pad = _pad_to(x2, 0, bm)
        dyp, _ = _pad_to(dy2, 0, bm)
        dx, dw1, dw2 = fused_mlp_bwd(xp, w1, w2, dyp, act=act, block_m=bm,
                                     block_h=bh, interpret=cfg.interpret)
        dx = dx[:m] if pad else dx
    else:
        dx, dw1, dw2 = ref.mlp_bwd_ref(x2, w1, w2, dy2, act=act)
    return dx.reshape(*lead, x.shape[-1]), dw1, dw2


def mlp_swiglu_bwd(x: jax.Array, wg, wu, wd, dy: jax.Array, *,
                   act: str = "silu", cfg: KernelConfig = KernelConfig()):
    """(dx, dwg, dwu, dwd) of (act(x @ wg) * (x @ wu)) @ wd -- gated
    multicast backward; x/dy may have leading batch dims."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    if cfg.use_pallas:
        m = x2.shape[0]
        bm, bh = _blocks(m, wg.shape[1], cfg)
        xp, pad = _pad_to(x2, 0, bm)
        dyp, _ = _pad_to(dy2, 0, bm)
        dx, dwg, dwu, dwd = fused_mlp_swiglu_bwd(
            xp, wg, wu, wd, dyp, act=act, block_m=bm, block_h=bh,
            interpret=cfg.interpret)
        dx = dx[:m] if pad else dx
    else:
        dx, dwg, dwu, dwd = ref.mlp_swiglu_bwd_ref(x2, wg, wu, wd, dy2,
                                                   act=act)
    return dx.reshape(*lead, x.shape[-1]), dwg, dwu, dwd


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, window=None,
              cfg: KernelConfig = KernelConfig()):
    if cfg.use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=cfg.block_q, block_k=cfg.block_k,
                               interpret=cfg.interpret)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v, *, valid_len=None,
                     cfg: KernelConfig = KernelConfig()):
    if cfg.use_pallas:
        return flash_decode(q, k, v, valid_len=valid_len,
                            block_s=cfg.block_s, interpret=cfg.interpret)
    return ref.decode_ref(q, k, v, valid_len=valid_len)


def paged_decode_attention(q, kp, vp, tables, *, valid_len, block_size: int,
                           layer=None, cfg: KernelConfig = KernelConfig()):
    """Decode attention straight out of the flat page pools (no dense-view
    gather): kp/vp (P, Hkv, D) or (P, G, A, Hkv, D) + layer=(g, a), tables
    (B, V), valid_len (B,).  The Pallas path resolves pages through the
    block table inside the kernel's index_map."""
    if cfg.use_pallas:
        return paged_flash_decode(q, kp, vp, tables, valid_len=valid_len,
                                  block_size=block_size, layer=layer,
                                  block_s=cfg.block_s,
                                  interpret=cfg.interpret)
    return ref.paged_decode_ref(q, kp, vp, tables, valid_len=valid_len,
                                block_size=block_size, layer=layer)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def reduce(x, *, op: str = "sum", cfg: KernelConfig = KernelConfig()):
    """Reduce axis 0 of (N, R, C)."""
    if cfg.use_pallas:
        return queue_reduce(x, op=op, block_rows=cfg.block_r,
                            interpret=cfg.interpret)
    return ref.reduce_ref(x, op)
