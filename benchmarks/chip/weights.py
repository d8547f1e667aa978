"""Seeded weights of a dense decoder, made by the benchmark.

Every value is a function of (seed, leaf name, layer) alone, so the program
gets the whole tree from one jitted call on the device, and the reference
makes the same values again one layer at a time, from the seed, without
taking anything that the program holds.

The tree has the program's layout (stacked layers, `blocks/sub0/...`); the
driver checks it against the program's own parameter shapes.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from common import seed_key

NORM_SPREAD = 0.1      # norm scales are 1 + 0.1 N(0, 1), not all ones
BIAS_SCALE = 0.02


def layer_shapes(a: dict) -> dict[str, tuple]:
    """Per-layer leaves (name -> shape) of one dense GQA + SwiGLU layer."""
    d, q, kv, f = (a["d_model"], a["n_heads"] * a["head_dim"],
                   a["n_kv_heads"] * a["head_dim"], a["d_ff"])
    shapes = {"ln1": (d,), "attn/wq": (d, q), "attn/wk": (d, kv),
              "attn/wv": (d, kv), "attn/wo": (q, d), "ln2": (d,),
              "mlp/wg": (d, f), "mlp/wu": (d, f), "mlp/wd": (f, d)}
    if a.get("qkv_bias"):
        shapes.update({"attn/bq": (q,), "attn/bk": (kv,), "attn/bv": (kv,)})
    return shapes


def top_shapes(a: dict) -> dict[str, tuple]:
    shapes = {"embed": (a["vocab"], a["d_model"]),
              "final_norm": (a["d_model"],)}
    if not a.get("tie_embeddings", True):
        shapes["unembed"] = (a["vocab"], a["d_model"])
    return shapes


def _scale(name: str, shape: tuple) -> float:
    if name in ("embed", "unembed"):
        return 0.02
    return shape[0] ** -0.5          # fan-in of a (in, out) matrix


def base_key(seed: int):
    """The weights' key; a jit argument, so one program serves every seed."""
    return seed_key(seed, "weights")


def leaf(key, name: str, shape: tuple, layer: int = -1,
         dtype=jnp.bfloat16) -> jax.Array:
    """One leaf (one layer of a stacked leaf) from the weights' key,
    traceable inside a jit."""
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    key = jax.random.fold_in(key, layer + 1)
    z = jax.random.normal(key, shape, jnp.float32)
    base = name.rsplit("/", 1)[-1]
    if base in ("ln1", "ln2", "final_norm"):
        v = 1.0 + NORM_SPREAD * z
    elif base.startswith("b"):
        v = BIAS_SCALE * z
    else:
        v = _scale(base, shape) * z
    return v.astype(dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def program_tree_traced(key, a: dict) -> dict:
    """The whole parameter tree in the program's layout (bf16), traceable
    inside a jit."""
    flat = {k: leaf(key, k, s) for k, s in top_shapes(a).items()}
    for k, s in layer_shapes(a).items():
        flat["blocks/sub0/" + k] = jnp.stack(
            [leaf(key, k, s, layer=i) for i in range(a["n_layers"])])
    return _nest(flat)


def program_tree(seed: int, a: dict) -> dict:
    """The whole parameter tree, made on the device in one jitted call."""
    return jax.jit(lambda k: program_tree_traced(k, a))(base_key(seed))


def layer_params(seed: int, a: dict, layer: int, dtype=jnp.float32) -> dict:
    """One layer's leaves, as the program holds them (bf16), in `dtype`."""
    fn = jax.jit(lambda k, i: {
        name: leaf(k, name, s, layer=i).astype(dtype)
        for name, s in layer_shapes(a).items()})
    return fn(base_key(seed), layer)


def top_params(seed: int, a: dict, dtype=jnp.float32) -> dict:
    fn = jax.jit(lambda k: {name: leaf(k, name, s).astype(dtype)
                            for name, s in top_shapes(a).items()})
    return fn(base_key(seed))


def check_layout(tree: dict, program_shapes: dict) -> None:
    """Raise unless `tree` has the program's parameter structure, shapes
    and dtypes (from `jax.eval_shape` of the program's own init)."""
    got = jax.tree_util.tree_structure(tree)
    want = jax.tree_util.tree_structure(program_shapes)
    if got != want:
        raise ValueError(f"weight tree {got} differs from the program's "
                         f"{want}")
    for g, w in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(program_shapes)):
        if g.shape != w.shape or g.dtype != w.dtype:
            raise ValueError(f"leaf {g.shape} {g.dtype} differs from the "
                             f"program's {w.shape} {w.dtype}")
