"""Lowerable training atomics: custom-vjp capture boundaries for the model
building blocks, so a traced `jax.grad` training step keeps its MLP / SwiGLU
/ attention blocks -- in BOTH directions -- as single recognizable graph
nodes instead of dissolving them into autodiff soup.

Each atom is an `atomic_vjp` pair (core/trace.py): the forward impl is the
kernels' jnp oracle (`ref.mlp_ref` / `ref.mlp_swiglu_ref`), the backward impl
is the matching oracle backward (`ref.mlp_bwd_ref` / `ref.mlp_swiglu_bwd_ref`
-- the same recompute-multicast math the Pallas kernels run).  The `lower=`
hints let the `lower_kernels` pass bind the nodes to the REAL kernels
(`fused_mlp_fwd` / `fused_mlp_swiglu_fwd` forward, `fused_mlp_bwd` /
`fused_mlp_swiglu_bwd` backward); unlowered execution replays the oracles, so
the two paths are numerically interchangeable.

Attention stays a single node per direction too.  Its impls are the jnp
chunked online-softmax and, backward, a recompute of it with cotangents
pulled through `jax.vjp` inside one node.  The hints bind the forward to
`flash_attention` and the backward to `flash_attention_bwd` (forward with
lse, then the dQ / dK-dV kernel pair, each recomputing its probability
tile); the window rides as a runtime operand the kernels scalar-prefetch.
Sites whose operands the kernels do not take (cross-attention with sq !=
skv, an untileable sequence) keep the closure, with the reason recorded.

`dataflow_training()` installs the atoms over `layers.mlp_block` and the
`chunked_attention` entrypoints for the duration of a trace:

    with atoms.dataflow_training():
        app = repro.compile(step_fn, (state, batch), mode="kitsune")
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.core.trace import atomic, atomic_vjp, attention_flops
from repro.kernels import ref
from . import encdec, layers, lm


def _flatten2(x):
    return x.reshape(-1, x.shape[-1])


# ---------------------------------------------------------------------------
# MLP / SwiGLU atoms (memoized per activation)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def mlp_atom(act: str):
    """(x, w1, w2) -> act(x @ w1) @ w2 as a differentiable atomic pair."""
    def fwd(x, w1, w2):
        y = ref.mlp_ref(_flatten2(x), w1, w2, act=act)
        return y.reshape(*x.shape[:-1], w2.shape[1])

    def bwd(x, w1, w2, dy):
        dx, dw1, dw2 = ref.mlp_bwd_ref(_flatten2(x), w1, w2, _flatten2(dy),
                                       act=act)
        return dx.reshape(x.shape), dw1, dw2

    return atomic_vjp(fwd, bwd, "matmul", name=f"mlp_{act}",
                      lower=("mlp_fwd", ("act", act)),
                      bwd_lower=("mlp_bwd", ("act", act)))


@functools.lru_cache(maxsize=None)
def swiglu_atom(act: str = "silu"):
    """(x, wg, wu, wd) -> (act(x@wg) * (x@wu)) @ wd as an atomic pair."""
    def fwd(x, wg, wu, wd):
        y = ref.mlp_swiglu_ref(_flatten2(x), wg, wu, wd, act=act)
        return y.reshape(*x.shape[:-1], wd.shape[1])

    def bwd(x, wg, wu, wd, dy):
        dx, dwg, dwu, dwd = ref.mlp_swiglu_bwd_ref(
            _flatten2(x), wg, wu, wd, _flatten2(dy), act=act)
        return dx.reshape(x.shape), dwg, dwu, dwd

    return atomic_vjp(fwd, bwd, "matmul", name=f"swiglu_{act}",
                      lower=("swiglu_fwd", ("act", act)),
                      bwd_lower=("swiglu_bwd", ("act", act)))


# ---------------------------------------------------------------------------
# paged decode atom (inference-only, no backward)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def paged_decode_atom(block_size: int):
    """(q, kp, vp, tables, valid) -> block-table-native decode attention.

    Inference-only atomic over the FLAT page pools: `kp`/`vp` are
    (pages*block_size, n_kv, d) row pools, `tables` is the (batch, v_blocks)
    per-slot block table and `valid` the per-slot live lengths.  The forward
    impl is the gather oracle (`ref.paged_decode_ref`); the `lower=` hint
    binds the node to the real split-K Pallas kernel
    (`kernels.paged_flash_decode`), which resolves `tables[b, c]` inside the
    index_map and never materializes the gathered view."""
    def fwd(q, kp, vp, tables, valid):
        return ref.paged_decode_ref(q, kp, vp, tables, valid_len=valid,
                                    block_size=block_size)

    def flops(in_avals, out_avals):
        b, hq, _, d = in_avals[0].shape
        s = in_avals[3].shape[1] * block_size  # v_blocks * page rows
        return 4.0 * b * hq * s * d

    return atomic(fwd, "attention", flops=flops,
                  name=f"paged_decode_b{block_size}",
                  lower=("paged_decode", ("block_size", block_size)))


# ---------------------------------------------------------------------------
# attention atom (flash-style recompute backward)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def attention_atom(causal: bool, chunk: int, windowed: bool = True,
                   orig=None):
    """(q, k, v[, window]) -> chunked attention as a differentiable atomic.

    A `windowed` atom takes the window as a runtime operand (per-layer scan
    xs), an array input past `n_diff` (zero cotangent); the other form has
    no window.  The two are separate atoms because a constant window would
    be folded into the atom by partial evaluation, and the kernel call
    reads the atom's operands by position.  The backward node recomputes
    the forward and pulls (dq, dk, dv) via jax.vjp -- one flash-recompute
    node; lowered, it runs the flash-attention backward kernels instead."""
    attn = orig or lm.chunked_attention

    def fwd(q, k, v, *window):
        return attn(q, k, v, causal=causal, window=window[0] if window
                    else None, chunk=chunk)

    def bwd(q, k, v, *rest):
        *window, dy = rest
        _, pull = jax.vjp(lambda q_, k_, v_: fwd(q_, k_, v_, *window),
                          q, k, v)
        return pull(dy)

    def flops(in_avals, out_avals):
        return attention_flops(in_avals, out_avals)

    hint = (("causal", causal), ("windowed", windowed))
    return atomic_vjp(fwd, bwd, "attention",
                      name=f"attn_c{int(causal)}{'w' if windowed else ''}",
                      n_diff=3,
                      flops=flops, bwd_flops=lambda i, o: 2 * flops(i, o),
                      lower=("attention_fwd", *hint),
                      bwd_lower=("attention_bwd", *hint))


# ---------------------------------------------------------------------------
# capture context
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def dataflow_training():
    """Route the model blocks through the training atoms for the duration of
    a trace.  Patches `layers.mlp_block` (dense/encdec MLPs; MoE keeps its
    scatter-dispatch path) and both `chunked_attention` entrypoints; the
    originals are restored on exit, so only capture sees the atoms.

    The patch is a PROCESS-WIDE module-global swap: enter this context only
    around tracing (milliseconds), never around execution, and not while
    other threads run models (a concurrent serve tick would pick up the
    oracle-backed atoms).  `compile_train_step` scopes it correctly."""
    orig_mlp = layers.mlp_block
    orig_attn_lm = lm.chunked_attention
    orig_attn_ed = encdec.chunked_attention

    def mlp_block(p, x, *, act="swiglu",
                  kernels=None, constrain=lambda t, _: t):
        if act == "swiglu":
            y = swiglu_atom("silu")(x, p["wg"], p["wu"], p["wd"])
        else:
            y = mlp_atom(act)(x, p["w1"], p["w2"])
        return constrain(y, "act_resid")

    def chunked_attention(q, k, v, *, causal=True, window=None, chunk=1024):
        if window is None:
            return attention_atom(causal, chunk, False, orig_attn_lm)(q, k, v)
        win = jnp.asarray(window, jnp.int32)
        return attention_atom(causal, chunk, True, orig_attn_lm)(q, k, v, win)

    layers.mlp_block = mlp_block
    lm.chunked_attention = chunked_attention
    encdec.chunked_attention = chunked_attention
    try:
        yield
    finally:
        layers.mlp_block = orig_mlp
        lm.chunked_attention = orig_attn_lm
        encdec.chunked_attention = orig_attn_ed
