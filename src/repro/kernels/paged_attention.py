"""Block-table-native paged-attention decode kernel.

`paged_flash_decode` is `flash_decode` with the dense-view gather pushed
*into* the kernel's address generation: K/V stay in the serving engine's flat
page pools and each split-K chunk resolves its pages through the per-slot
block table (a scalar-prefetch operand, so the table drives the BlockSpec
index_map -- vLLM-PagedAttention / FlashInfer style).  Per-tick KV traffic
drops from a full O(view) pool->view copy plus an O(view) kernel read to a
single O(table) read: consecutive grid steps whose index_map resolves to the
same physical page (e.g. the shared null page beyond a short slot's
allocation) re-use the already-fetched block instead of re-DMAing it.

The per-chunk math is copied verbatim from `_decode_kernel_dyn` (one-shot
max/exp/sum over the chunk, partials merged by `combine_partials`), so for a
given `block_s` the output is **bitwise-equal** to gathering the view with
`rows = table*bs + offsets` and running `flash_decode` on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, combine_partials, page_block_s


def _paged_decode_kernel(tbl_ref, vl_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, k_buf, v_buf, *,
                         scale, block_s, ppc, bs, d, hkv):
    """Grid (b, n_chunks, pages_per_chunk); the page axis is innermost so
    the VMEM chunk buffers persist while the chunk's pages stream in.  One
    program serves every kv head of its batch element: a page block is the
    site's (bs, hkv*d) row slab, lane-aligned per head, so no block dim is
    a lone head.  The (o, m, l) partial for the chunk is emitted on the last
    page -- per head, the math is `_decode_kernel_dyn`'s, unchanged, so
    partials are bitwise-identical to the gather path's."""
    b = pl.program_id(0)
    c = pl.program_id(1)
    p = pl.program_id(2)
    k_buf[pl.ds(p * bs, bs), :] = k_ref[...]
    v_buf[pl.ds(p * bs, bs), :] = v_ref[...]

    @pl.when(p == ppc - 1)
    def _chunk_done():
        valid = vl_ref[b]
        for h in range(hkv):
            q = q_ref[0, h]                     # (group, d)
            k = k_buf[:, h * d:(h + 1) * d]     # (block_s, d) table-resolved
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            base = c * block_s
            ki = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(ki < valid, s, NEG_INF)
            m_c = jnp.max(s, axis=-1, keepdims=True)
            pe = jnp.exp(s - m_c)
            l_c = jnp.sum(pe, axis=-1, keepdims=True)
            o_c = jnp.dot(pe.astype(v_buf.dtype), v_buf[:, h * d:(h + 1) * d],
                          preferred_element_type=jnp.float32)
            o_ref[0, 0, h] = o_c
            m_ref[0, 0, h] = m_c
            l_ref[0, 0, h] = l_c


def paged_flash_decode(q: jax.Array, kp: jax.Array, vp: jax.Array,
                       tables: jax.Array, *, valid_len,
                       block_size: int, layer: tuple | None = None,
                       scale: float | None = None,
                       block_s: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Decode attention straight out of the page pools.

    q: (B, Hq, 1, D); kp/vp: flat page pools, either a single attention
    site's rows (P, Hkv, D) or the engine's full pools (P, G, A, Hkv, D)
    with `layer=(g, a)` selecting the site (static ints -- they pin the
    site's column block, so only that site's rows move).
    tables: (B, V) physical page ids per slot (row p covers pool rows
    [p*block_size, (p+1)*block_size)); entries beyond a slot's allocation
    point at the reserved null page 0.  valid_len: per-slot (B,) position
    clock; positions >= valid are masked exactly as `_decode_kernel_dyn`.

    `block_s` (split-K chunk, rows) must be a multiple of `block_size`; it
    is clamped/aligned via `page_block_s`.  On the TPU, `block_size` must
    be a multiple of 8 and D of 128 (the tile of a page block).
    """
    b, hq, one, d = q.shape
    assert one == 1
    if kp.ndim == 5:
        assert layer is not None, "5D pools need layer=(g, a)"
        g_i, a_i = layer
        site = g_i * kp.shape[2] + a_i
        hkv = kp.shape[3]
    else:
        assert kp.ndim == 3 and layer is None
        site = 0
        hkv = kp.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bs = int(block_size)
    v_blocks = tables.shape[1]
    s_len = v_blocks * bs
    scale = scale if scale is not None else d ** -0.5
    block_s = page_block_s(s_len, bs, block_s)
    ppc = block_s // bs                 # pages per split-K chunk (program)
    n_s = s_len // block_s

    # row-major views: one pool row holds every site's (hkv, d) slab, so
    # column block `site` of width hkv*d is this site's K/V for that row
    k2 = kp.reshape(kp.shape[0], -1)
    v2 = vp.reshape(vp.shape[0], -1)
    qr = q.reshape(b, hkv, group, d)
    vl = jnp.asarray(valid_len, jnp.int32)
    if vl.ndim == 0:
        vl = jnp.broadcast_to(vl, (b,))
    tbl = jnp.asarray(tables, jnp.int32)

    def kv_map(bi, c, p, tbl_ref, vl_ref):
        return (tbl_ref[bi, c * ppc + p], site)

    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             block_s=block_s, ppc=ppc, bs=bs, d=d, hkv=hkv)
    part = lambda bi, c, p, *_: (bi, c, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_s, ppc),
        in_specs=[
            pl.BlockSpec((1, hkv, group, d), lambda bi, c, p, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((bs, hkv * d), kv_map),
            pl.BlockSpec((bs, hkv * d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hkv, group, d), part),
            pl.BlockSpec((1, 1, hkv, group, 1), part),
            pl.BlockSpec((1, 1, hkv, group, 1), part),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_s, hkv * d), kp.dtype),
            pltpu.VMEM((block_s, hkv * d), vp.dtype),
        ],
    )
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_s, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_s, hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_s, hkv, group, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tbl, vl, qr, k2, v2)
    out = combine_partials(o, m, l)     # (b, hkv, group, d)
    return out.reshape(b, hq, 1, d).astype(q.dtype)
