"""Seeded weights, made by the benchmark in the layout that the
configuration's reference module declares.

Every value is a function of (seed, leaf name, layer) alone, so the program
gets the whole tree from one jitted call on the device, and the reference
makes the same values again one layer at a time, from the seed, without
taking anything that the program holds.

The reference (`reference/<name>.py`) names the leaves: `top_shapes(a)`,
and `layer_shapes(a, i)` for layer i, named as the program names them below
`blocks/sub<j>/`.  The program stacks its layers in P kinds of sub-layer
(`blocks/sub0` ... `blocks/sub<P-1>`; P is 1 for a dense stack, 2 for dense
layers between expert layers), so layer i lies in `blocks/sub<i % P>` at
index i // P.  The driver takes P from the program's own parameter shapes
and checks the tree against them.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from common import seed_key

NORM_SPREAD = 0.1      # norm scales are 1 + 0.1 N(0, 1), not all ones
BIAS_SCALE = 0.02
# (in, out) matrices, or stacks of them (experts), by leaf name; the scale
# is their fan-in
MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "w1", "w2", "router")
BIASES = ("bq", "bk", "bv")


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
    if base in ("embed", "unembed"):
        v = 0.02 * z
    elif base.startswith("ln") or base.endswith("norm"):
        v = 1.0 + NORM_SPREAD * z
    elif base in BIASES:
        v = BIAS_SCALE * z
    elif base in MATRICES:
        v = shape[-2] ** -0.5 * z
    else:
        raise ValueError(f"leaf {name!r}: weights.py has no init scale for "
                         f"a leaf named {base!r}")
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


def program_tree_traced(key, a: dict, ref, subs: int) -> dict:
    """The whole parameter tree in the program's layout (bf16), with its
    layers stacked in `subs` kinds of sub-layer; traceable inside a jit."""
    flat = {k: leaf(key, k, s) for k, s in ref.top_shapes(a).items()}
    for j in range(subs):
        layers = range(j, a["n_layers"], subs)
        shapes = ref.layer_shapes(a, j)
        for i in layers:
            if ref.layer_shapes(a, i) != shapes:
                raise ValueError(f"layers {j} and {i} of blocks/sub{j} "
                                 f"declare different leaves")
        for k, s in shapes.items():
            flat[f"blocks/sub{j}/{k}"] = jnp.stack(
                [leaf(key, k, s, layer=i) for i in layers])
    return _nest(flat)


def program_tree(seed: int, a: dict, ref, subs: int) -> dict:
    """The whole parameter tree, made on the device in one jitted call."""
    return jax.jit(lambda k: program_tree_traced(k, a, ref, subs))(
        base_key(seed))


def layer_params(seed: int, a: dict, layer: int, ref,
                 dtype=jnp.float32) -> dict:
    """One layer's leaves, as the program holds them (bf16), in `dtype`."""
    shapes = ref.layer_shapes(a, layer)
    fn = jax.jit(lambda k, i: {name: leaf(k, name, s, layer=i).astype(dtype)
                               for name, s in shapes.items()})
    return fn(base_key(seed), layer)


def top_params(seed: int, a: dict, ref, dtype=jnp.float32) -> dict:
    fn = jax.jit(lambda k: {name: leaf(k, name, s).astype(dtype)
                            for name, s in ref.top_shapes(a).items()})
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
