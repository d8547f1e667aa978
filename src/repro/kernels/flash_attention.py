"""Dataflow attention Pallas kernels.

Attention *is* a synchronous-dataflow pipeline: K/V tiles stream through VMEM
past a running online-softmax state (m, l, acc) -- a 2-deep queue between a
QK^T producer stage and a PV consumer stage.  The (S, S) score matrix never
exists in HBM (the BSP baseline writes it twice).

Variants:
  * flash_attention      -- prefill/training; causal and sliding-window masks,
                            GQA (q-head groups share a kv head).
  * flash_decode         -- single-token decode with the KV sequence *split
                            over the grid* (the paper's Fig 2(b): reduction-dim
                            parallelism instead of batch parallelism), partial
                            (o, m, l) merged by a queue_reduce-style combine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def tile_candidates(sq: int, skv: int) -> list[dict]:
    """Autotune grid for flash_attention: (block_q, block_k) pairs dividing
    (sq, skv) exactly; the historical 128/128 default is always present."""
    bqs = [bq for bq in (64, 128, 256) if sq % bq == 0] or [min(128, sq)]
    bks = [bk for bk in (64, 128, 256) if skv % bk == 0] or [min(128, skv)]
    cands = [{"block_q": bq, "block_k": bk} for bq in bqs for bk in bks]
    default = {"block_q": min(128, sq), "block_k": min(128, skv)}
    if default not in cands:
        cands.append(default)
    return cands


def page_block_s(s_len: int, page_size: int, block_s: int | None) -> int:
    """Align a split-K chunk size to page boundaries: the largest multiple of
    `page_size` that is <= min(block_s or 256, s_len) and divides `s_len`
    exactly (s_len is always a whole number of pages, so this terminates at
    `page_size`).  paged_flash_decode programs own whole pages."""
    want = block_s if block_s is not None else 256
    want = max(page_size, (min(want, s_len) // page_size) * page_size)
    while s_len % want:
        want -= page_size
    return want


def decode_tile_candidates(s_len: int,
                           page_size: int | None = None) -> list[dict]:
    """Autotune grid for the decode split-K chunk size.

    With `page_size` (the paged kernel), every candidate is a whole number
    of pages -- `block_s` doubles as pages-per-program (`block_s //
    page_size`), so the grid sweeps 1, 2, 4, ... pages per split-K chunk.
    """
    if page_size is not None:
        cands = [{"block_s": m * page_size}
                 for m in (1, 2, 4, 8, 16, 32, 64)
                 if m * page_size <= s_len and s_len % (m * page_size) == 0]
        default = {"block_s": page_block_s(s_len, page_size, None)}
        if default not in cands:
            cands.append(default)
        return cands
    bss = [bs for bs in (128, 256, 512) if s_len % bs == 0]
    default = {"block_s": min(256, s_len)}
    cands = [{"block_s": bs} for bs in bss]
    if default not in cands:
        cands.append(default)
    return cands


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int, n_k: int):
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (block_q, d)
    k = k_ref[0]                       # (block_k, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q0 = pl.program_id(1) * block_q
    k0 = kv * block_k
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    ki = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= qi >= ki
    if window is not None:
        mask &= qi - ki < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D); Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    n_q, n_k = sq // block_q, skv // block_k

    grid = (b * hq, n_q, n_k)
    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)
    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, g=group: (bh // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, g=group: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)


# ---------------------------------------------------------------------------
# decode: split-K over the KV sequence (Fig 2b)
# ---------------------------------------------------------------------------

def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, scale, n_s,
                   valid_len):
    schunk = pl.program_id(1)
    q = q_ref[0]                        # (hq_group, d) -- one token, grouped heads
    k = k_ref[0]                        # (block_s, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    base = schunk * k.shape[0]
    ki = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ki < valid_len, s, NEG_INF)
    m_c = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_c)
    l_c = jnp.sum(p, axis=-1, keepdims=True)
    o_c = jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                  preferred_element_type=jnp.float32)
    o_ref[0, 0] = o_c
    m_ref[0, 0] = m_c
    l_ref[0, 0] = l_c


def _decode_kernel_dyn(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                       *, scale, hkv):
    """Decode chunk kernel with a RUNTIME per-sequence valid length.

    `valid_ref` is the scalar-prefetched (B,) valid-length vector -- the
    serving engine's per-slot position clock (each slot attends to exactly
    its own [0, valid) cache range; a refilled slot never sees the previous
    occupant's stale entries).  Program bh serves batch element bh // hkv."""
    bh = pl.program_id(0)
    schunk = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    base = schunk * k.shape[0]
    ki = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ki < valid_ref[bh // hkv], s, NEG_INF)
    m_c = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_c)
    l_c = jnp.sum(p, axis=-1, keepdims=True)
    o_c = jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                  preferred_element_type=jnp.float32)
    o_ref[0, 0] = o_c
    m_ref[0, 0] = m_c
    l_ref[0, 0] = l_c


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 valid_len: int | jax.Array | None = None,
                 scale: float | None = None,
                 block_s: int = 256, interpret: bool = False) -> jax.Array:
    """Decode attention: q (B, Hq, 1, D), kv (B, Hkv, S, D).

    The KV sequence is split over the grid into independent partial-softmax
    chunks (each emits (o, m, l)); the final merge is the queue_reduce
    combine.  This is the reduction-dimension parallelism the paper uses to
    'ease pressure on batch size'.

    `valid_len` masks cache positions >= valid: a static python int
    specializes the kernel; a traced scalar or a per-sequence (B,) vector
    (the serving engine's per-slot position clock) is fed as a runtime
    operand instead, so one compiled kernel serves every mix of slot
    positions.
    """
    b, hq, one, d = q.shape
    _, hkv, s_len, _ = k.shape
    assert one == 1
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    valid_len = s_len if valid_len is None else valid_len
    block_s = min(block_s, s_len)
    assert s_len % block_s == 0
    n_s = s_len // block_s

    qr = q.reshape(b * hkv, group, d)   # group heads share this kv head
    kr = k.reshape(b * hkv, s_len, d)
    vr = v.reshape(b * hkv, s_len, d)
    # `*_` absorbs the scalar-prefetch operand of the runtime-length form
    in_specs = [
        pl.BlockSpec((1, group, d), lambda bh, j, *_: (bh, 0, 0)),
        pl.BlockSpec((1, block_s, d), lambda bh, j, *_: (bh, j, 0)),
        pl.BlockSpec((1, block_s, d), lambda bh, j, *_: (bh, j, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, group, d), lambda bh, j, *_: (bh, j, 0, 0)),
        pl.BlockSpec((1, 1, group, 1), lambda bh, j, *_: (bh, j, 0, 0)),
        pl.BlockSpec((1, 1, group, 1), lambda bh, j, *_: (bh, j, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * hkv, n_s, group, d), jnp.float32),
        jax.ShapeDtypeStruct((b * hkv, n_s, group, 1), jnp.float32),
        jax.ShapeDtypeStruct((b * hkv, n_s, group, 1), jnp.float32),
    ]
    if isinstance(valid_len, int):
        kern = functools.partial(_decode_kernel, scale=scale, n_s=n_s,
                                 valid_len=valid_len)
        o, m, l = pl.pallas_call(
            kern, grid=(b * hkv, n_s), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret)(qr, kr, vr)
    else:
        vl = jnp.asarray(valid_len, jnp.int32)
        if vl.ndim == 0:
            vl = jnp.broadcast_to(vl, (b,))
        kern = functools.partial(_decode_kernel_dyn, scale=scale, hkv=hkv)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * hkv, n_s), in_specs=in_specs,
            out_specs=out_specs)
        o, m, l = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret)(vl, qr, kr, vr)
    out = combine_partials(o, m, l)     # (b*hkv, group, d)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def combine_partials(o: jax.Array, m: jax.Array, l: jax.Array,
                     axis: int = 1) -> jax.Array:
    """Merge split-softmax partials: the queue_reduce 'final' stage.

    o: (..., n_chunks, ..., d) partial weighted sums; m, l: running max / sum.
    Also used across mesh shards by serve/ (distributed flash-decode)."""
    m_g = jnp.max(m, axis=axis, keepdims=True)
    w = jnp.exp(m - m_g)
    l_g = jnp.sum(l * w, axis=axis)
    o_g = jnp.sum(o * w, axis=axis)
    l_g = jnp.where(l_g == 0.0, 1.0, l_g)
    return o_g / l_g
