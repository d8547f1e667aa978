"""Dataflow attention Pallas kernels.

Attention *is* a synchronous-dataflow pipeline: K/V tiles stream through VMEM
past a running online-softmax state (m, l, acc) -- a 2-deep queue between a
QK^T producer stage and a PV consumer stage.  The (S, S) score matrix never
exists in HBM (the BSP baseline writes it twice).

Variants:
  * flash_attention      -- prefill/training; causal and sliding-window masks
                            (the window a runtime scalar), GQA (q-head groups
                            share a kv head); tiles wholly outside the masks
                            get no compute and no fetch.
  * flash_attention_lse  -- the same, also returning the row log-sum-exp.
  * flash_attention_bwd  -- the training backward: a dQ kernel and a dK/dV
                            kernel, each recomputing its probability tile
                            from q, k and the lse (the paper's Fig 2(c)
                            multicast), dK/dV summed over the GQA group.
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


def train_block(s: int) -> int | None:
    """The fixed tile rule of the training kernels (`flash_attention_lse`
    and the dQ / dK-dV pair): square blocks of 512 rows, else 256, else
    128, else the whole sequence where it is at most 512 long; None where
    none of these tiles `s` (the site then keeps its closure).  A rule, not
    a search, so the training sites add no tile search to set-up.  On one
    v5e at Phi-3-medium's attention (40 / 10 heads of 128, S 2048) blocks
    of 512 ran the forward and the backward 1.6-1.8x faster than 256, and
    1024 no faster."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    return s if s <= 512 else None


def _window_operand(window, s: int) -> jax.Array:
    """The window as the kernels' scalar-prefetched int32 (1,) operand: a
    static int, None (no window) or a traced scalar alike, clamped to `s`
    (a window of `s` or more masks nothing), so one compiled kernel serves
    windowed and global layers."""
    w = jnp.asarray(s if window is None else window, jnp.int32)
    return jnp.minimum(w, s).reshape(1)


def _tile(i, j, w, *, causal, bq, bk):
    """(visible, masked) of the (q block i, kv block j) tile: whether any
    of its pairs is attended, and whether any of them is masked."""
    q0, k0 = i * bq, j * bk
    q1, k1 = q0 + bq - 1, k0 + bk - 1
    visible = q0 - k1 < w
    full = q1 - k0 < w
    if causal:
        visible = jnp.logical_and(visible, q1 >= k0)
        full = jnp.logical_and(full, q0 >= k1)
    return visible, jnp.logical_not(full)


def _run_tile(i, j, w, body, **geom):
    """Run `body(masked)` on a visible tile, with the mask only where the
    tile straddles the diagonal or the window's edge; tiles above the
    diagonal or behind the window get no compute."""
    visible, masked = _tile(i, j, w, **geom)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(masked)))
    def _full():
        body(False)

    @pl.when(jnp.logical_and(visible, masked))
    def _edge():
        body(True)


def _mask(i, j, w, shape, *, causal, bq, bk):
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = qi - ki < w
    if causal:
        mask = jnp.logical_and(mask, qi >= ki)
    return mask


def _kv_range(i, w, *, causal, bq, bk, n_k):
    """First and last kv block that q block i attends to."""
    q0 = i * bq
    lo = jnp.maximum(q0 - w + 1, 0) // bk
    hi = jnp.minimum((q0 + bq - 1) // bk, n_k - 1) if causal else n_k - 1
    return lo, hi


def _q_range(j, w, *, causal, bq, bk, n_q):
    """First and last q block that attends to kv block j."""
    k0 = j * bk
    lo = k0 // bq if causal else 0
    hi = jnp.minimum((k0 + bk + w - 2) // bq, n_q - 1)
    return lo, hi


def _clamp(x, lo, hi):
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _dot_nt(a, b):
    """a @ b.T with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# Lanes of the running softmax statistics: each row's max and sum are kept
# replicated across one vreg's 128 lanes, so broadcasting them over a
# (rows, block) tile copies whole vregs instead of broadcasting a column.
STAT_LANES = 128


def _bcast(stat, n: int):
    """A lane-replicated (rows, STAT_LANES) statistic as (rows, n)."""
    if n % stat.shape[1] == 0:
        return jnp.tile(stat, (1, n // stat.shape[1]))
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], n))


def _fwd_kernel(w_ref, q_ref, k_ref, v_ref, o_ref, *rest, scale, geom, n_k,
                with_lse):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (m_ref, l_ref, acc_ref), lse_ref = rest, None
    i, j = pl.program_id(1), pl.program_id(2)
    w = w_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        s = _dot_nt(q_ref[0], k_ref[0]) * scale
        if masked:
            s = jnp.where(_mask(i, j, w, s.shape, **geom), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _bcast(m_new, s.shape[1]))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _bcast(alpha, acc_ref.shape[1]) + \
            jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _run_tile(i, j, w, update, **geom)

    @pl.when(j == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / _bcast(l, acc_ref.shape[1])).astype(
            o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_ref[...] + jnp.log(l)


def _fwd(q, k, v, window, *, causal, scale, block_q, block_k, interpret,
         with_lse):
    """The forward on flattened heads: o (B*Hq, Sq, D) and, `with_lse`, the
    f32 row log-sum-exp (B*Hq, Sq, STAT_LANES), lane-replicated."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    n_q, n_k = sq // bq, skv // bk
    geom = dict(causal=causal, bq=bq, bk=bk)

    def q_map(bh, i, j, w_ref):
        return bh, i, 0

    def kv_map(bh, i, j, w_ref):
        # tiles outside the masks are clamped onto a neighbour the grid
        # fetches anyway, so they cost no DMA
        lo, hi = _kv_range(i, w_ref[0], n_k=n_k, **geom)
        return bh // group, _clamp(j, lo, hi), 0

    out_specs = [pl.BlockSpec((1, bq, d), q_map)]
    out_shape = [jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, bq, STAT_LANES), q_map))
        out_shape.append(jax.ShapeDtypeStruct((b * hq, sq, STAT_LANES),
                                              jnp.float32))
    kern = functools.partial(_fwd_kernel, scale=scale, geom=geom, n_k=n_k,
                             with_lse=with_lse)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b * hq, n_q, n_k),
        in_specs=[pl.BlockSpec((1, bq, d), q_map),
                  pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d), kv_map)],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bq, STAT_LANES), jnp.float32),
                        pltpu.VMEM((bq, STAT_LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)])
    return pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_PARAMS, name="flash_attention",
        interpret=interpret,
    )(_window_operand(window, max(sq, skv)), q.reshape(b * hq, sq, d),
      k.reshape(b * hkv, skv, d), v.reshape(b * hkv, skv, d))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D); Hq % Hkv == 0.

    `window` (keys with q_pos - k_pos >= window are masked) is None, a
    static int or a traced int32 scalar: it is a runtime operand either
    way.  Causal tiles above the diagonal are skipped and not fetched."""
    b, hq, sq, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    (o,) = _fwd(q, k, v, window, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k, interpret=interpret,
                with_lse=False)
    return o.reshape(b, hq, sq, d)


def flash_attention_lse(q, k, v, *, causal: bool = True, window=None,
                        scale: float | None = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """`flash_attention` that also returns the f32 row log-sum-exp of the
    scaled, masked scores, (B, Hq, Sq): what the backward kernels
    recompute each probability tile from."""
    b, hq, sq, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    o, lse = _fwd(q, k, v, window, causal=causal, scale=scale,
                  block_q=block_q, block_k=block_k, interpret=interpret,
                  with_lse=True)
    return o.reshape(b, hq, sq, d), lse[..., 0].reshape(b, hq, sq)


# ---------------------------------------------------------------------------
# backward: a dQ kernel and a dK/dV kernel (Fig 2c multicast)
# ---------------------------------------------------------------------------

def _probs(q, k, lse, scale, mask):
    """The probability tile, recomputed from q, k and the row lse."""
    s = _dot_nt(q, k) * scale
    p = jnp.exp(s - _bcast(lse, s.shape[1]))
    return p if mask is None else jnp.where(mask, p, 0.0)


def _dq_kernel(w_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, *, scale, geom, n_k):
    i, j = pl.program_id(1), pl.program_id(2)
    w = w_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        k = k_ref[0]
        mask = (_mask(i, j, w, (q_ref.shape[1], k.shape[0]), **geom)
                if masked else None)
        p = _probs(q_ref[0], k, lse_ref[0], scale, mask)
        ds = p * (_dot_nt(do_ref[0], v_ref[0]) -
                  _bcast(di_ref[0], p.shape[1]))
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    _run_tile(i, j, w, update, **geom)

    @pl.when(j == n_k - 1)
    def _done():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(w_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, geom, n_q, n_t):
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % n_q
    w = w_ref[0]

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(masked):
        q, do = q_ref[0], do_ref[0]
        mask = (_mask(i, j, w, (q.shape[0], k_ref.shape[1]), **geom)
                if masked else None)
        p = _probs(q, k_ref[0], lse_ref[0], scale, mask)
        dv_acc[...] += _dot_tn(p.astype(do.dtype), do)
        ds = p * (_dot_nt(do, v_ref[0]) - _bcast(di_ref[0], p.shape[1]))
        dk_acc[...] += _dot_tn(ds.astype(q.dtype), q)

    _run_tile(i, j, w, update, **geom)

    @pl.when(t == n_t - 1)
    def _done():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, do, *, causal: bool = True, window=None,
                        scale: float | None = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """(dq, dk, dv) of `flash_attention(q, k, v)` against the cotangent
    `do`, in the primals' dtypes.

    Runs the forward with lse, then delta = rowsum(do * o) in f32, then two
    kernels that each recompute their probability tile from q, k and the
    lse -- the multicast of paper Fig 2(c): one over (q head, q block, kv
    block) accumulating dQ, one over (kv head, kv block, group x q block)
    accumulating dK and dV over the q heads that share the kv head (GQA),
    so no per-q-head partials reach HBM."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, bk = min(block_q, sq), min(block_k, skv)
    n_q, n_k = sq // bq, skv // bk
    geom = dict(causal=causal, bq=bq, bk=bk)
    o, lse = _fwd(q, k, v, window, causal=causal, scale=scale, block_q=bq,
                  block_k=bk, interpret=interpret, with_lse=True)
    dor = do.reshape(b * hq, sq, d)
    di = jnp.sum(dor.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)
    di = jnp.broadcast_to(di, lse.shape)           # lane-replicated, as lse
    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    w = _window_operand(window, max(sq, skv))

    def row_map(bh, i, j, w_ref):
        return bh, i, 0

    def kv_map(bh, i, j, w_ref):
        lo, hi = _kv_range(i, w_ref[0], n_k=n_k, **geom)
        return bh // group, _clamp(j, lo, hi), 0

    rows = pl.BlockSpec((1, bq, d), row_map)
    stat = pl.BlockSpec((1, bq, STAT_LANES), row_map)
    kvs = pl.BlockSpec((1, bk, d), kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, geom=geom, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * hq, n_q, n_k),
            in_specs=[rows, kvs, kvs, rows, stat, stat], out_specs=rows,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        compiler_params=_PARAMS, name="flash_attention_dq",
        interpret=interpret,
    )(w, qr, kr, vr, dor, lse, di)

    n_t = group * n_q

    def q_map(bkv, j, t, w_ref):
        lo, hi = _q_range(j, w_ref[0], n_q=n_q, **geom)
        return bkv * group + t // n_q, _clamp(t % n_q, lo, hi), 0

    def own_map(bkv, j, t, w_ref):
        return bkv, j, 0

    rows = pl.BlockSpec((1, bq, d), q_map)
    stat = pl.BlockSpec((1, bq, STAT_LANES), q_map)
    kvs = pl.BlockSpec((1, bk, d), own_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, geom=geom, n_q=n_q,
                          n_t=n_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * hkv, n_k, n_t),
            in_specs=[rows, kvs, kvs, rows, stat, stat],
            out_specs=[kvs, kvs],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(kr.shape, k.dtype),
                   jax.ShapeDtypeStruct(vr.shape, v.dtype)],
        compiler_params=_PARAMS, name="flash_attention_dkv",
        interpret=interpret,
    )(w, qr, kr, vr, dor, lse, di)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


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
            name="flash_decode",
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
            name="flash_decode",
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
