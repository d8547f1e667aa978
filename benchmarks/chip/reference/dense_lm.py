"""Plain float32 reference of a dense GQA decoder with SwiGLU MLPs (the
Phi-3 and Qwen1.5 families), for the checks that decide `correct`.

Written from the published architecture in straightforward jax.numpy; it
imports nothing of the program and takes none of its arrays.  Weights come
from `weights.py`, made again here from the seed.  Every matrix product runs
in float32 at `highest` precision.  Departures from the published
architecture, each one the program's convention, are listed in the
configuration files' `departures`:

  * token embeddings are multiplied by sqrt(d_model) before the first layer;
  * parameters are held in bfloat16, the configuration's dtype, between
    optimizer steps (the program keeps no float32 master copy).

`quant` is applied to both operands of every matrix product; the control
puts float8 there, the precision below the configuration's bfloat16.

As every module under `reference/`, it also says what the model holds and
what a trained token costs: `top_shapes`, `layer_shapes` (the leaves of one
layer, as the program names them below `blocks/sub<j>/`) and
`train_flops_per_token`, which `weights.py` and the driver read.
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

import flops
import weights

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def top_shapes(a: dict) -> dict[str, tuple]:
    shapes = {"embed": (a["vocab"], a["d_model"]),
              "final_norm": (a["d_model"],)}
    if not a.get("tie_embeddings", True):
        shapes["unembed"] = (a["vocab"], a["d_model"])
    return shapes


def layer_shapes(a: dict, i: int) -> dict[str, tuple]:
    """Leaves (name -> shape) of layer i; every layer is one dense GQA +
    SwiGLU layer."""
    d, q, kv, f = (a["d_model"], a["n_heads"] * a["head_dim"],
                   a["n_kv_heads"] * a["head_dim"], a["d_ff"])
    shapes = {"ln1": (d,), "attn/wq": (d, q), "attn/wk": (d, kv),
              "attn/wv": (d, kv), "attn/wo": (q, d), "ln2": (d,),
              "mlp/wg": (d, f), "mlp/wu": (d, f), "mlp/wd": (f, d)}
    if a.get("qkv_bias"):
        shapes.update({"attn/bq": (q,), "attn/bk": (kv,), "attn/bv": (kv,)})
    return shapes


def matmul_params(a: dict) -> int:
    """Weights that multiply every token: the layers and the output head
    (the embedding is a lookup)."""
    d, q = a["d_model"], a["n_heads"] * a["head_dim"]
    kv, f = a["n_kv_heads"] * a["head_dim"], a["d_ff"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return a["n_layers"] * per_layer + a["vocab"] * d


def train_flops_per_token(a: dict, seq: int) -> float:
    """Forward and backward (3x the forward) per trained token, with causal
    attention over (seq + 1) / 2 keys on average; recomputation is not
    counted."""
    return 3.0 * (2.0 * matmul_params(a) + flops.attn_flops(a, (seq + 1) / 2))


def _scaled_cast(x, dtype):
    """x through `dtype` with one scale for the tensor (its largest
    magnitude maps to the format's largest)."""
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    """float8 as fp8 training runs it: operands in e4m3 and their gradients
    in e5m2, each tensor with its own scale."""
    return _scaled_cast(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_cast(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _ident(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x: (S, H, D); rotate-half RoPE at positions pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv                      # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant, block=512):
    """Causal GQA over one sequence.  q: (S, H, D); k, v: (S, Hkv, D).
    Query blocks bound the score tile at (block, S) per head."""
    s, h, d = q.shape
    hkv = k.shape[1]
    grp = h // hkv
    kq, vq = quant(k), quant(v)
    block = min(block, s)
    n = s // block

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        qg = quant(qi).reshape(block, hkv, grp, d)
        sc = jnp.einsum("qhgd,khd->hgqk", qg, kq, precision=HI) * d ** -0.5
        qpos = i * block + jnp.arange(block)
        mask = qpos[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", quant(p), vq, precision=HI)
        return o.reshape(block, h * d)

    return jax.lax.map(one, jnp.arange(n)).reshape(s, h * d)


def layer(x, p, a, pos, quant):
    """One pre-norm decoder layer over one sequence x: (S, D)."""
    eps = a["rms_norm_eps"]
    hd, h, hkv = a["head_dim"], a["n_heads"], a["n_kv_heads"]
    y = rms_norm(x, p["ln1"], eps)
    q = _mm(y, p["attn/wq"], quant) + p.get("attn/bq", 0.0)
    k = _mm(y, p["attn/wk"], quant) + p.get("attn/bk", 0.0)
    v = _mm(y, p["attn/wv"], quant) + p.get("attn/bv", 0.0)
    s = x.shape[0]
    q = rope(q.reshape(s, h, hd), pos, a["rope_theta"])
    k = rope(k.reshape(s, hkv, hd), pos, a["rope_theta"])
    o = attention(q, k, v.reshape(s, hkv, hd), quant)
    x = x + _mm(o, p["attn/wo"], quant)
    y = rms_norm(x, p["ln2"], eps)
    f = _mm(jax.nn.silu(_mm(y, p["mlp/wg"], quant)) * _mm(y, p["mlp/wu"],
                                                           quant),
            p["mlp/wd"], quant)
    return x + f


def embed(table, tokens):
    return table[tokens].astype(F32) * np.sqrt(table.shape[1])


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, the program's step written out
# ---------------------------------------------------------------------------

def loss_fn(params, tokens, a, hp, quant):
    """Mean next-token cross entropy over a (B, S) batch, plus the z-loss
    term `hp["z_loss"] * mean(logsumexp^2)`."""
    top, layers = params
    b, s = tokens.shape
    pos = jnp.arange(s)
    chunk = hp["xent_chunk"]
    tot = totz = 0.0
    for r in range(b):
        x = embed(top["embed"], tokens[r])
        for p in layers:
            x = jax.checkpoint(functools.partial(layer, a=a, pos=pos,
                                                 quant=quant))(x, p)
        x = rms_norm(x, top["final_norm"], a["rms_norm_eps"])
        head = top.get("unembed", top["embed"])
        xs, tg = x[:-1], tokens[r, 1:]
        pad = (-xs.shape[0]) % chunk
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
        tg = jnp.pad(tg, (0, pad), constant_values=-1)

        @jax.checkpoint
        def part(c):
            xc, tc = c
            lg = _mm(xc, head.T, quant)
            lse = jax.nn.logsumexp(lg, axis=-1)
            ll = jnp.take_along_axis(lg, jnp.maximum(tc, 0)[:, None], 1)[:, 0]
            ok = tc >= 0
            return (jnp.sum(jnp.where(ok, lse - ll, 0.0)),
                    jnp.sum(jnp.where(ok, lse * lse, 0.0)))

        l1, l2 = jax.lax.map(part, (xs.reshape(-1, chunk, xs.shape[1]),
                                    tg.reshape(-1, chunk)))
        tot, totz = tot + jnp.sum(l1), totz + jnp.sum(l2)
    n = b * (s - 1)
    return tot / n + hp["z_loss"] * totz / n


def lr_at(step: int, hp: dict) -> float:
    """The cosine schedule with linear warm-up, at optimizer step `step`
    (1-based, as the update counts it)."""
    peak, warm, total = hp["lr"], hp["warmup"], hp["total_steps"]
    floor = hp["lr_floor"]
    if step < warm:
        return peak * (step + 1) / warm
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * frac)))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _adam_leaf(p, g, m, v, scale_lr, b1, b2, eps, wd, step):
    scale, lr = scale_lr[0], scale_lr[1]
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** step)
    vh = v / (1 - b2 ** step)
    pf = p.astype(F32)
    new = (pf - lr * (mh / (jnp.sqrt(vh) + eps) + wd * pf)).astype(p.dtype)
    return new, m, v


def _flat(params) -> dict:
    top, layers = params
    out = dict(top)
    for i, p in enumerate(layers):
        out.update({f"{k}@{i}": v for k, v in p.items()})
    return out


def _unflat(flat: dict, n_layers: int):
    top = {k: v for k, v in flat.items() if "@" not in k}
    layers = [{} for _ in range(n_layers)]
    for k, v in flat.items():
        if "@" in k:
            name, i = k.split("@")
            layers[int(i)][name] = v
    return top, layers


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))))


def train(seed: int, a: dict, batches: list, hp: dict, steps: int,
          quant=_ident) -> dict:
    """`steps` optimizer steps from the seeded weights on `batches`.
    Returns the losses, each leaf's clipped gradient norm at step 1 and each
    leaf's change over the steps (leaf names `<leaf>@<layer>`)."""
    n = a["n_layers"]
    me = sys.modules[__name__]
    params = (weights.top_params(seed, a, me, jnp.bfloat16),
              [weights.layer_params(seed, a, i, me, jnp.bfloat16)
               for i in range(n)])
    p0 = {k: np.asarray(v.astype(F32)) for k, v in _flat(params).items()}

    @jax.jit
    def grad_fn(pb, tokens):
        # gradients with respect to the float32 values of the parameters
        p32 = jax.tree.map(lambda t: t.astype(F32), pb)
        return jax.value_and_grad(loss_fn)(p32, tokens, a, hp, quant)

    flat = _flat(params)
    m = {k: np.zeros(v.shape, np.float32) for k, v in flat.items()}
    vv = {k: np.zeros(v.shape, np.float32) for k, v in flat.items()}
    losses, gnorm1 = [], {}
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            loss, g = grad_fn(_unflat(flat, n), batches[t])
            losses.append(float(loss))
            gflat = _flat(g)
            gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                    for x in gflat.values())))
            scale = min(1.0, hp["max_grad_norm"] / (gn + 1e-9))
            if t == 0:
                gnorm1 = {k: _norm(x) * scale for k, x in gflat.items()}
            sl = jnp.asarray([scale, lr_at(t + 1, hp)], F32)
            for k in list(flat):
                flat[k], mk, vk = _adam_leaf(
                    flat[k], gflat[k], jnp.asarray(m[k]), jnp.asarray(vv[k]),
                    sl, hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"],
                    t + 1)
                m[k], vv[k] = np.asarray(mk), np.asarray(vk)
            del g, gflat
    delta = {k: float(np.linalg.norm(np.asarray(flat[k].astype(F32)) - p0[k]))
             for k in flat}
    return {"losses": losses, "grad_norms": gnorm1, "delta_norms": delta}
