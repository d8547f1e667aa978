"""Logical-axis sharding rules (MaxText-style) with divisibility fallbacks.

Mesh axes: ("pod",)? + ("data", "model").  Policy (DESIGN.md SS4):
  * weights: one tensor dim -> "model" (TP), the other -> "data" (FSDP
    storage; GSPMD all-gathers on demand, overlapped under scan).
  * activations: batch -> ("pod","data"); the residual stream's *sequence*
    dim -> "model" between layers (Megatron-style sequence parallelism).
  * every rule silently skips a mesh axis the dim doesn't divide -- this is
    the fallback chain that handles qwen's 40 heads / yi's 56 heads / hymba's
    32001 vocab on a 16-wide model axis.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


@dataclass
class Sharder:
    """Resolves logical dim specs to PartitionSpecs on a concrete mesh."""
    mesh: Mesh
    # attention activation sharding: "seq" (sequence/context parallel,
    # default) or "heads" (Megatron TP).  Measured head-to-head in
    # EXPERIMENTS.md SS Perf iteration 7: with a seq-sharded residual
    # stream, head-sharded attention re-gathers the sequence every layer --
    # switching pixtral train_4k to "seq" cut the collective term 28.1 ->
    # 3.0 s and took grok train to its compute roofline (frac 0.53 -> 1.0).
    # Decode (seq=1) falls back to head sharding automatically.
    attn_sharding: str = "seq"

    @property
    def batch_axes(self):
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    def _fit(self, dim: int, axes):
        """Return axes if dim divides their product, else None."""
        if axes is None:
            return None
        if dim % _axis_size(self.mesh, axes) == 0:
            return axes if not (isinstance(axes, tuple) and len(axes) == 1) else axes[0]
        # single-axis fallback within a multi-axis spec
        if isinstance(axes, tuple):
            for a in axes:
                if dim % _axis_size(self.mesh, a) == 0:
                    return a
        return None

    def spec(self, dims: list[tuple[int, Any]]) -> P:
        """dims: [(size, requested_axes_or_None), ...] -> PartitionSpec."""
        used: set[str] = set()
        out = []
        for size, want in dims:
            got = self._fit(size, want)
            flat = got if isinstance(got, tuple) else (got,) if got else ()
            if got is not None and not (set(flat) & used):
                out.append(got)
                used.update(flat)
            else:
                out.append(None)
        return P(*out)

    def named(self, dims) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(dims))

    def _heads_dims(self, x):
        if x.ndim != 4:
            return None
        b = self.batch_axes
        m = self.mesh.shape["model"]
        heads_ok = x.shape[2] % m == 0
        seq_ok = x.shape[1] % m == 0
        if seq_ok and (self.attn_sharding == "seq" or not heads_ok):
            return [(x.shape[0], b), (x.shape[1], "model"),
                    (x.shape[2], None), (x.shape[3], None)]
        if heads_ok:
            return [(x.shape[0], b), (x.shape[1], None),
                    (x.shape[2], "model"), (x.shape[3], None)]
        return [(x.shape[0], b), (x.shape[1], None),
                (x.shape[2], None), (x.shape[3], None)]

    # -- activation constraint kinds (called from model code) -------------
    def constrain(self, x: jax.Array, kind: str) -> jax.Array:
        b = self.batch_axes
        m = "model"
        table = {
            # (B, S, D): sequence-parallel residual stream
            "act_resid": [(x.shape[0], b), (x.shape[1], m), (x.shape[2], None)],
            # (B, S, H, hd): heads -> model; fallback to sequence sharding
            # when the head count doesn't divide (qwen 40H / yi 56H / hymba
            # 25H on a 16-wide axis) -- DESIGN.md SS4 divisibility chain.
            "act_heads": self._heads_dims(x),
            "act_kv_heads": self._heads_dims(x),
            # (B, S, F) mlp hidden
            "act_mlp": [(x.shape[0], b), (x.shape[1], None), (x.shape[2], m)],
            # (E, C, D) dispatched expert tokens
            "act_experts": [(x.shape[0], m), (x.shape[1], None),
                            (x.shape[2], None)],
            # (G, E, C, D): groups with batch, experts -> model (EP)
            "act_grouped_experts": [(x.shape[0], b), (x.shape[1], m),
                                    (x.shape[2], None), (x.shape[3], None)]
            if x.ndim == 4 else None,
            # (G, E, C, F) expert hidden: experts -> model when divisible,
            # else the wide FFN dim -> model (grok's 8 experts left a
            # (G,8,C,32768) f32 hidden sharded only over G: 21 GiB/chip)
            "act_expert_hidden": [(x.shape[0], b), (x.shape[1], m),
                                  (x.shape[2], None), (x.shape[3], m)]
            if x.ndim == 4 else None,
            # (E, G*C, F) flattened expert hidden
            "act_expert_hidden_flat": [(x.shape[0], m), (x.shape[1], b),
                                       (x.shape[2], m)]
            if x.ndim == 3 else None,
            # (E, din, dout): pin the compute layout of expert weights so
            # GSPMD doesn't reshard them (fwd AND weight-grad bwd) -- the
            # llama4 train cell emitted ~200 full-E f32 weight reshards
            # before this (EXPERIMENTS.md SS Perf iteration 3)
            "expert_weights": [(x.shape[0], m), (x.shape[1], None),
                               (x.shape[2], m)]
            if x.ndim == 3 else None,
            # (B, S, V)
            "logits": [(x.shape[0], b), (x.shape[1], None), (x.shape[2], m)],
        }
        dims = table.get(kind)
        if dims is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.named(dims))

    # -- parameter shardings ----------------------------------------------
    def param_spec(self, path: str, shape: tuple[int, ...]) -> NamedSharding:
        """Sharding for a parameter leaf, keyed on its pytree path.

        Stacked-layer leading dims (scan) are never sharded.  The last two
        meaningful dims get (fsdp="data", tp="model") in an orientation that
        puts "model" on the *contraction-free* dim of each projection.
        """
        b = "data" if "data" in self.mesh.shape else None
        m = "model"
        name = path.split("/")[-1]
        nd = len(shape)

        def lead(n):
            return [(shape[i], None) for i in range(n)]

        if name in ("embed", "unembed", "table"):
            # (V, D): vocab -> model, embed -> data(FSDP)
            return self.named(lead(nd - 2) + [(shape[-2], m), (shape[-1], b)])
        if name in ("wq", "wk", "wv", "in_x", "in_z", "wg", "wu", "w1", "up",
                    "skip_g", "w_gates"):
            # (D, out): out -> model, D -> data
            return self.named(lead(nd - 2) + [(shape[-2], b), (shape[-1], m)])
        if name in ("wo", "wd", "w2", "down", "out"):
            # (in, D): in -> model, D -> data
            return self.named(lead(nd - 2) + [(shape[-2], m), (shape[-1], b)])
        if name in ("router", "w_bcdt", "wif"):
            return self.named(lead(nd - 2) + [(shape[-2], b), (shape[-1], None)])
        if name in ("bq", "bk", "bv"):
            return self.named(lead(nd - 1) + [(shape[-1], m)])
        if nd >= 3 and "experts" in path:
            # (E, din, dout): experts -> model (EP) when divisible, else dout
            e_axes = self._fit(shape[-3], m)
            if e_axes is not None:
                return self.named(lead(nd - 3) + [(shape[-3], m),
                                                  (shape[-2], b), (shape[-1], None)])
            return self.named(lead(nd - 3) + [(shape[-3], None),
                                              (shape[-2], b), (shape[-1], m)])
        # norms / scalars / gates: replicate
        return self.named([(s, None) for s in shape])

    def params_shardings(self, params) -> Any:
        """Tree of NamedShardings matching a param pytree."""
        flat = jax.tree_util.tree_flatten_with_path(params)[0]

        def path_str(kp):
            return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in kp)

        specs = {path_str(kp): self.param_spec(path_str(kp), v.shape)
                 for kp, v in flat}
        treedef = jax.tree_util.tree_structure(params)
        return jax.tree_util.tree_unflatten(
            treedef, [specs[path_str(kp)] for kp, v in flat])

    def state_shardings(self, state) -> dict:
        """Shardings for a whole train state {"params", "opt"} (train/step.py
        `make_train_state`): an optimizer leaf kept at a parameter's
        position with that parameter's shape (Adam moments, Adafactor's
        unfactored second moment) takes the parameter's sharding; the step
        counter and factored or quantized moments are replicated."""
        params, opt = state["params"], state["opt"]
        p_sh = self.params_shardings(params)
        rep = self.replicated()

        def moments(p, sh, sub):
            return jax.tree.map(
                lambda x: sh if x.shape == p.shape else rep, sub)

        inner = jax.tree.map(moments, params, p_sh, opt.inner)
        return {"params": p_sh, "opt": opt._replace(step=rep, inner=inner)}

    def data_sharding(self, ndim: int = 2) -> NamedSharding:
        """(B, S, ...) batch over (pod, data)."""
        return NamedSharding(self.mesh, P(self.batch_axes, *([None] * (ndim - 1))))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def cache_sharding(self, batch: int, n_kv: int) -> NamedSharding:
        """KV cache (L, B, Hkv, S, D): batch -> (pod,data); heads -> model
        when divisible, else sequence-shard (distributed flash-decode)."""
        if n_kv % self.mesh.shape["model"] == 0:
            return NamedSharding(self.mesh, P(None, self.batch_axes, "model", None, None))
        return NamedSharding(self.mesh, P(None, self.batch_axes, None, "model", None))


class NullSharder:
    """No-mesh stand-in: every constraint is the identity (single-device)."""

    def constrain(self, x, kind):
        return x

    def params_shardings(self, params):
        return None


NULL = NullSharder()
