"""Jaxpr -> Graph importer: the compiler's capture front-end.

This is the reproduction's analogue of Kitsune's Dynamo capture (paper SS5):
`trace(fn, *example_args)` runs `jax.make_jaxpr` and imports the resulting
jaxpr into the operator-graph IR, so `repro.compile(fn, example_inputs)`
works on ANY jax callable -- in particular every architecture in the
`repro.configs` zoo -- and the whole pass pipeline (selection, Algorithm 1,
Algorithm 2, cost model) consumes it unchanged.

Fidelity contract: every imported node carries an evaluation closure
(`attrs["_eval"]`) binding the EXACT source primitive + params, so executing
the graph in any mode (bsp / vertical / kitsune) is numerically identical to
calling the original function.  The closure is an implementation carrier:
fingerprints (executable-cache keys) come from the stable public attrs
`prim` / `params` instead, so re-tracing the same function re-uses cached
executables.

Import rules:

  * dot_general / conv           -> matmul / conv   (MXU)
  * reduce_sum (single fp axis)  -> reduce           -- generic semantics,
    eligible for the split-reduction pass; all other reductions keep their
    closure and are never split
  * reshape/transpose/broadcast/slice/convert/...  -> reshape (free)
  * gather/sort/top_k            -> gather (excluded from sf-nodes, SS5.1)
  * scatter*/dynamic_update_slice-> scatter (excluded)
  * everything else              -> elementwise (VPU)
  * captured constants (closure weights, folded literals) -> const nodes,
    auto-fed at run time by the TracedApp artifact
  * lax.scan                     -> UNROLLED into per-iteration nodes (the
    layer loop of every zoo model becomes a real dataflow graph); scans
    bigger than `max_unroll_eqns` stay opaque single nodes
  * multi-output primitives      -> one tuple-valued node + free projections
  * jit of a registered atomic (see `atomic()`) -> ONE node of the
    registered kind (e.g. fused attention), flops from the registry;
    `atomic_vjp()` registers a custom-vjp PAIR so `jax.grad` traces keep
    both the forward and the backward as single (kernel-lowerable) nodes
  * other jit / custom_jvp / custom_vjp / remat -> inlined
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from .graph import Graph, Node, TensorSpec

# A scan is unrolled iff trip_count * len(body_eqns) stays under this budget;
# beyond it the scan becomes one opaque node (still numerically exact).
MAX_UNROLL_EQNS = 8192
# Consts up to this size are deduplicated by value (zeros/iota tiles repeat
# across unrolled iterations); larger ones only by object identity.  It also
# bounds constant folding: a literal-only eqn with a larger result (zeros of
# a weight's shape, a (B, S, vocab) iota) stays in the graph, so its program
# makes it on the device instead of the app holding a materialized copy in
# device memory for its whole life.
_CONST_DEDUP_BYTES = 1 << 16

# Primitive -> op-kind classification ---------------------------------------

_MXU_PRIMS = {"dot_general": "matmul", "conv_general_dilated": "conv"}

_REDUCE_PRIMS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin"}

_FREE_PRIMS = {"reshape", "broadcast_in_dim", "transpose", "squeeze",
               "expand_dims", "rev", "copy", "convert_element_type",
               "stop_gradient", "slice", "pad", "reduce_precision",
               "bitcast_convert_type"}

_GATHER_PRIMS = {"gather", "dynamic_slice", "take", "sort", "top_k",
                 "approx_top_k", "argsort"}

_SCATTER_PRIMS = {"scatter", "scatter-add", "scatter-mul", "scatter-min",
                  "scatter-max", "dynamic_update_slice", "select_and_scatter",
                  "select_and_scatter_add"}

# flops per element for the elementwise fallback kind
_TRANSCENDENTAL = {"exp", "exp2", "expm1", "log", "log1p", "log2", "tanh",
                   "logistic", "sin", "cos", "tan", "erf", "erfc", "erf_inv",
                   "rsqrt", "sqrt", "pow", "cbrt", "atan2", "digamma",
                   "lgamma"}

_INLINE_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")
_INLINE_PRIMS = {"jit", "closed_call", "core_call", "xla_call",
                 "custom_jvp_call", "custom_vjp_call",
                 "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
                 "remat", "remat2", "checkpoint", "custom_transpose_call",
                 "name"}


# ---------------------------------------------------------------------------
# atomic sub-jaxpr registry (recognizable fused blocks, e.g. attention)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AtomicSpec:
    kind: str
    flops: Callable[[list, list], float] | None = None  # (in_avals, out_avals)
    # Optional kernel-lowering hint consumed by core/lower.py: a stable
    # nested tuple, e.g. ("mlp_bwd", ("act", "gelu")).  The hint pins the
    # node's semantics for the matcher (the atomic registry is the source of
    # truth), so lowering does not need to reverse-engineer the sub-jaxpr.
    lower: tuple | None = None


_ATOMICS: dict[str, AtomicSpec] = {}
_ATOMIC_PREFIX = "repro.atomic"


def atomic(fn: Callable, kind: str, *,
           flops: Callable[[list, list], float] | None = None,
           static_argnames: Sequence[str] = (),
           lower: tuple | None = None,
           name: str | None = None) -> Callable:
    """Wrap `fn` so the tracer imports any call to it as ONE node of `kind`.

    The wrapper jits `fn` under a marker name; when the tracer meets the
    resulting jit eqn it emits a single graph node (resource class and
    pattern code of `kind`) whose eval closure runs the whole sub-jaxpr --
    this is how fused attention stays one "attention" op instead of
    dissolving into its einsum/softmax soup.

    `lower` tags the node with a kernel-lowering hint (`attrs["lower_hint"]`)
    that core/lower.py matches onto a real Pallas kernel -- the hint must
    fully determine the kernel call's static config (the tracer bakes it into
    the fingerprint attrs, so differently-hinted atomics never share
    executables)."""
    if kind not in ("attention", "matmul", "elementwise", "reduce", "norm",
                    "softmax", "conv", "gather"):
        raise ValueError(f"unsupported atomic kind {kind!r}")
    stem = name or getattr(fn, "__name__", "fn")
    marker = f"{_ATOMIC_PREFIX}[{kind}].{stem}"

    def _marked(*args, **kwargs):
        return fn(*args, **kwargs)

    _marked.__name__ = marker
    _marked.__qualname__ = marker
    _ATOMICS[marker] = AtomicSpec(kind, flops, lower)
    return jax.jit(_marked, static_argnames=tuple(static_argnames))


def _zero_cotangent(x):
    """Symbolic-zero gradient for a non-differentiable primal (float0 for
    integer operands, per the custom_vjp contract)."""
    if jnp.issubdtype(jnp.result_type(x), jnp.integer):
        return np.zeros(jnp.shape(x), jax.dtypes.float0)
    return jnp.zeros_like(x)


def atomic_vjp(fn: Callable, bwd: Callable, kind: str, *,
               bwd_kind: str | None = None,
               n_diff: int | None = None,
               flops: Callable[[list, list], float] | None = None,
               bwd_flops: Callable[[list, list], float] | None = None,
               lower: tuple | None = None,
               bwd_lower: tuple | None = None,
               name: str | None = None) -> Callable:
    """A differentiable atomic: BOTH directions stay single nodes.

    `fn(*primals)` is the forward; `bwd(*primals, cotangent)` returns the
    tuple of gradients for the first `n_diff` primals (default: all).  Each
    side is wrapped as its own marked atomic, glued together with
    `jax.custom_vjp`, so `jax.grad` through the wrapper produces a jaxpr in
    which the forward imports as one `kind` node and the backward as one
    `bwd_kind` node -- the custom-vjp boundary the training trace needs so
    backward MLP/attention blocks survive capture as recognizable (and
    kernel-lowerable) units instead of dissolving into autodiff soup.

    Primals past `n_diff` (e.g. a runtime attention-window operand) get
    zero cotangents appended OUTSIDE the atomic -- float0 for integer
    operands, which must never enter the graph IR.

    All arguments must be arrays (pre-bind statics with functools.partial;
    encode them in `name`/`lower` so distinct configs get distinct markers)."""
    stem = name or getattr(fn, "__name__", "fn")
    fwd_m = atomic(fn, kind, flops=flops, lower=lower, name=stem)
    bwd_m = atomic(bwd, bwd_kind or kind, flops=bwd_flops, lower=bwd_lower,
                   name=f"{stem}_bwd")

    @jax.custom_vjp
    def wrapped(*args):
        return fwd_m(*args)

    def fwd_rule(*args):
        return fwd_m(*args), args

    def bwd_rule(res, dy):
        out = bwd_m(*res, dy)
        grads = tuple(out) if isinstance(out, (tuple, list)) else (out,)
        if n_diff is not None:
            grads = grads[:n_diff] + tuple(
                _zero_cotangent(x) for x in res[n_diff:])
        return grads

    wrapped.defvjp(fwd_rule, bwd_rule)
    wrapped.__name__ = stem
    return wrapped


def attention_flops(in_avals: list, out_avals: list) -> float:
    """Default estimator for atomic attention: q (B,Hq,S,D) x k (B,Hkv,T,D)."""
    shaped = [a for a in in_avals if getattr(a, "ndim", 0) == 4]
    if len(shaped) < 2:
        return sum(2.0 * getattr(a, "size", 0) for a in in_avals)
    q, k = shaped[0], shaped[1]
    b, hq, s, d = q.shape
    t = k.shape[2]
    return 2 * 2.0 * b * hq * s * t * d


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _spec(aval) -> TensorSpec:
    return TensorSpec(tuple(aval.shape), str(aval.dtype))


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def _out_bytes(eqn) -> int:
    return sum(math.prod(v.aval.shape) * np.dtype(v.aval.dtype).itemsize
               for v in eqn.outvars)


def _stable_params(params: dict) -> str:
    """Deterministic, address-free repr of eqn params (fingerprint input)."""
    parts = []
    for k in sorted(params):
        v = params[k]
        if isinstance(v, (jex_core.ClosedJaxpr, jex_core.Jaxpr)):
            inner = v.jaxpr if isinstance(v, jex_core.ClosedJaxpr) else v
            digest = hashlib.sha256(str(inner).encode()).hexdigest()[:12]
            parts.append((k, f"jaxpr:{digest}"))
        elif callable(v):
            parts.append((k, getattr(v, "__name__", type(v).__name__)))
        else:
            r = repr(v)
            if " at 0x" in r:
                r = r.split(" at 0x")[0]
            parts.append((k, r))
    return repr(parts)


def _sub_jaxprs(params: dict) -> list["jex_core.Jaxpr"]:
    """Every jaxpr-valued param (jit `jaxpr`, while `body_jaxpr` /
    `cond_jaxpr`, cond `branches` tuple, ...), as open jaxprs."""
    found = []
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, jex_core.ClosedJaxpr):
                found.append(u.jaxpr)
            elif isinstance(u, jex_core.Jaxpr):
                found.append(u)
    return found


def jaxpr_flops(jaxpr: "jex_core.Jaxpr") -> float:
    """Rough FLOP count of a jaxpr (dot_generals + elementwise visits),
    recursing through nested jaxprs (scan bodies scaled by trip count, while
    bodies counted once, cond branches worst-case); drives opaque-node cost
    tags."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "scan":
            total += (jaxpr_flops(eqn.params["jaxpr"].jaxpr)
                      * max(int(eqn.params.get("length", 1)), 1))
        elif name == "cond":
            total += max((jaxpr_flops(j) for j in _sub_jaxprs(eqn.params)),
                         default=0.0)
        else:
            total += sum(jaxpr_flops(j) for j in _sub_jaxprs(eqn.params))
            if name not in _FREE_PRIMS:
                total += sum(float(np.prod(v.aval.shape))
                             for v in eqn.outvars if not _is_dropvar(v))
    return total


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    ls = eqn.invars[0].aval.shape
    rs = eqn.invars[1].aval.shape
    batch = math.prod(ls[i] for i in lb) or 1
    k = math.prod(ls[i] for i in lc) or 1
    m = math.prod(d for i, d in enumerate(ls) if i not in lc and i not in lb) or 1
    n = math.prod(d for i, d in enumerate(rs) if i not in rc and i not in rb) or 1
    return 2.0 * batch * m * k * n


def _stable_literal(v) -> str:
    """Address-free repr of a baked literal operand.  Literals live inside
    the eval closure (not as graph edges), so they MUST show up in the
    fingerprint attrs or `x + 1.0` and `x + 2.0` would share a cache key."""
    a = np.asarray(v)
    if a.size <= 16:
        return f"{a.dtype}:{a.shape}:{a.tolist()!r}"
    digest = hashlib.sha256(a.tobytes()).hexdigest()[:12]
    return f"{a.dtype}:{a.shape}:sha{digest}"


def _make_eval(prim, params: dict, literal_slots: dict[int, Any], n_in: int):
    """Closure evaluating `prim` with the traced operands re-slotted around
    the baked literals; returns a tuple for multi-result primitives."""
    def ev(*args):
        full = []
        ai = 0
        for i in range(n_in):
            if i in literal_slots:
                full.append(literal_slots[i])
            else:
                full.append(args[ai])
                ai += 1
        out = prim.bind(*full, **params)
        return tuple(out) if prim.multiple_results else out
    return ev


# ---------------------------------------------------------------------------
# the importer
# ---------------------------------------------------------------------------

@dataclass
class TracedFunction:
    """A jax callable imported into the Graph IR.

    `consts` hold the captured weights/folded constants keyed by their const
    node names -- the executor feeds them alongside the positional inputs."""
    graph: Graph
    consts: dict[str, jax.Array]
    in_names: list[str]
    in_tree: Any
    out_names: list[str]
    out_tree: Any
    closed_jaxpr: Any = None

    def feeds(self, *args) -> dict[str, jax.Array]:
        flat, tree = jax.tree_util.tree_flatten(args)
        if tree != self.in_tree:
            raise TypeError(f"argument structure {tree} does not match the "
                            f"traced structure {self.in_tree}")
        if len(flat) != len(self.in_names):
            raise TypeError(f"expected {len(self.in_names)} array args, "
                            f"got {len(flat)}")
        out = dict(zip(self.in_names, flat))
        out.update(self.consts)
        return out

    def unflatten_outputs(self, outputs: dict[str, jax.Array]):
        return jax.tree_util.tree_unflatten(
            self.out_tree, [outputs[n] for n in self.out_names])


class _Importer:
    def __init__(self, name: str, max_unroll_eqns: int,
                 roll_scans: bool = False):
        self.g = Graph(name)
        self.consts: dict[str, jax.Array] = {}
        self.max_unroll_eqns = max_unroll_eqns
        self.roll_scans = roll_scans
        self._by_id: dict[int, str] = {}
        self._by_val: dict[tuple, str] = {}
        # arrays registered in _by_id must stay alive: a freed temporary's
        # id() can be reused by an unrelated array, aliasing its const node
        self._keepalive: list[Any] = []
        self._n = 0

    def fresh(self, stem: str) -> str:
        self._n += 1
        return f"{stem}_{self._n}"

    # -- consts ------------------------------------------------------------
    def add_const(self, val) -> str:
        val = jnp.asarray(val)
        if id(val) in self._by_id:
            return self._by_id[id(val)]
        vkey = None
        if val.size * val.dtype.itemsize <= _CONST_DEDUP_BYTES:
            vkey = (str(val.dtype), tuple(val.shape),
                    np.asarray(val).tobytes())
            if vkey in self._by_val:
                name = self._by_val[vkey]
                self._by_id[id(val)] = name
                self._keepalive.append(val)
                return name
        name = self.fresh("const")
        self.g.add(Node(name, "const", [],
                        TensorSpec(tuple(val.shape), str(val.dtype))))
        self.consts[name] = val
        self._by_id[id(val)] = name
        if vkey is not None:
            self._by_val[vkey] = name
        return name

    # -- jaxpr walking -----------------------------------------------------
    def run_jaxpr(self, jaxpr: "jex_core.Jaxpr", const_names: list[str],
                  arg_names: list[str]) -> list[str]:
        env: dict[Any, str] = {}
        for var, nm in zip(jaxpr.constvars, const_names):
            env[var] = nm
        for var, nm in zip(jaxpr.invars, arg_names):
            env[var] = nm
        for eqn in jaxpr.eqns:
            self.eqn(eqn, env)
        outs = []
        for var in jaxpr.outvars:
            if isinstance(var, jex_core.Literal):
                outs.append(self.add_const(var.val))
            else:
                outs.append(env[var])
        return outs

    def _materialize(self, eqn, env) -> list[str]:
        """All invars as node names (literals become consts)."""
        names = []
        for v in eqn.invars:
            if isinstance(v, jex_core.Literal):
                names.append(self.add_const(v.val))
            else:
                names.append(env[v])
        return names

    def eqn(self, eqn, env: dict) -> None:
        prim = eqn.primitive
        name = prim.name
        # 1. constant folding: no traced operands and a small result ->
        #    evaluate at trace time
        if (all(isinstance(v, jex_core.Literal) for v in eqn.invars)
                and _out_bytes(eqn) <= _CONST_DEDUP_BYTES):
            vals = prim.bind(*[v.val for v in eqn.invars], **eqn.params)
            vals = list(vals) if prim.multiple_results else [vals]
            for var, val in zip(eqn.outvars, vals):
                if not _is_dropvar(var):
                    env[var] = self.add_const(val)
            return
        # 2. higher-order eqns
        if name == "scan":
            self._scan(eqn, env)
            return
        if name in _INLINE_PRIMS:
            spec = _ATOMICS.get(eqn.params.get("name", ""))
            if spec is not None:
                self._atomic(eqn, env, spec)
                return
            inner = self._inner_jaxpr(eqn.params)
            if inner is not None:
                closed = (inner if isinstance(inner, jex_core.ClosedJaxpr)
                          else jex_core.ClosedJaxpr(inner, ()))
                const_names = [self.add_const(c) for c in closed.consts]
                outs = self.run_jaxpr(closed.jaxpr, const_names,
                                      self._materialize(eqn, env))
                for var, nm in zip(eqn.outvars, outs):
                    if not _is_dropvar(var):
                        env[var] = nm
                return
        if name in ("while", "cond"):
            self._opaque(eqn, env)
            return
        # 3. leaf primitive
        self._leaf(eqn, env)

    @staticmethod
    def _inner_jaxpr(params: dict):
        for k in _INLINE_JAXPR_PARAMS:
            v = params.get(k)
            if isinstance(v, (jex_core.ClosedJaxpr, jex_core.Jaxpr)):
                return v
        return None

    # -- node emission -----------------------------------------------------
    def _emit(self, eqn, env, *, kind: str, flops: float,
              attrs: dict | None = None, ev=None, inputs=None) -> None:
        prim = eqn.primitive
        outvars = list(eqn.outvars)
        out_avals = [v.aval for v in outvars]
        lits = ""
        if inputs is None:
            literal_slots = {i: v.val for i, v in enumerate(eqn.invars)
                             if isinstance(v, jex_core.Literal)}
            inputs = [env[v] for v in eqn.invars
                      if not isinstance(v, jex_core.Literal)]
            lits = repr([(i, _stable_literal(v))
                         for i, v in sorted(literal_slots.items())])
            if ev is None:
                ev = _make_eval(prim, eqn.params, literal_slots,
                                len(eqn.invars))
        base = {"prim": prim.name, "params": _stable_params(eqn.params)}
        if lits and lits != "[]":
            base["lits"] = lits
        if attrs:
            base.update(attrs)
        multi = prim.multiple_results or len(outvars) > 1
        spec = _spec(out_avals[0])
        if multi:
            base["n_outs"] = len(outvars)
            # one TensorSpec per node: carry the LARGEST output so the byte
            # accounting is a lower bound that is not systematically tiny
            spec = max((_spec(a) for a in out_avals), key=lambda s: s.nbytes)
        if ev is not None:
            base["_eval"] = ev
        node = self.g.add(Node(self.fresh(prim.name.replace("-", "_")), kind,
                               list(inputs), spec, float(flops), 0.0, base))
        if not multi:
            if not _is_dropvar(outvars[0]):
                env[outvars[0]] = node.name
            return
        for i, var in enumerate(outvars):
            if _is_dropvar(var):
                continue
            proj = self.g.add(Node(
                self.fresh(f"{node.name}.o{i}"), "reshape", [node.name],
                _spec(var.aval), 0.0, 0.0,
                {"prim": "proj", "params": str(i),
                 "_eval": (lambda t, _i=i: t[_i])}))
            env[var] = proj.name

    def _leaf(self, eqn, env) -> None:
        prim = eqn.primitive
        name = prim.name
        out_aval = eqn.outvars[0].aval
        out_size = float(np.prod(out_aval.shape)) if out_aval.shape else 1.0
        if name == "dot_general":
            self._emit(eqn, env, kind="matmul", flops=_dot_general_flops(eqn))
        elif name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            self._emit(eqn, env, kind="conv",
                       flops=2.0 * out_size * math.prod(rhs[1:]))
        elif name in _REDUCE_PRIMS:
            in_aval = eqn.invars[0].aval
            axes = tuple(np.atleast_1d(eqn.params.get(
                "axes", eqn.params.get("axis", 0))).tolist())
            red = math.prod(in_aval.shape[a] for a in axes) if axes else 1
            attrs = {"axis": int(axes[0]) if axes else 0,
                     "red_size": int(red), "keepdims": False}
            simple_sum = (name == "reduce_sum" and len(axes) == 1
                          and np.issubdtype(in_aval.dtype, np.floating)
                          and str(in_aval.dtype) == str(out_aval.dtype)
                          and not isinstance(eqn.invars[0], jex_core.Literal))
            if simple_sum:
                # generic kind semantics == jnp.sum(axis): leave the closure
                # off so the split-reduction pass may rewrite it (Algorithm 1)
                self._emit(eqn, env, kind="reduce",
                           flops=float(np.prod(in_aval.shape)), attrs=attrs,
                           ev=None, inputs=[env[eqn.invars[0]]])
            else:
                self._emit(eqn, env, kind="reduce",
                           flops=float(np.prod(in_aval.shape)), attrs=attrs)
        elif name == "concatenate":
            self._emit(eqn, env, kind="concat", flops=0.0,
                       attrs={"axis": int(eqn.params.get("dimension", 0))})
        elif name in _GATHER_PRIMS:
            self._emit(eqn, env, kind="gather", flops=0.0)
        elif name in _SCATTER_PRIMS:
            self._emit(eqn, env, kind="scatter", flops=out_size)
        elif name in _FREE_PRIMS:
            self._emit(eqn, env, kind="reshape", flops=0.0)
        else:
            fpe = 4.0 if name in _TRANSCENDENTAL else 1.0
            self._emit(eqn, env, kind="elementwise", flops=fpe * out_size,
                       attrs={"fn": "identity"})

    def _atomic(self, eqn, env, spec: AtomicSpec) -> None:
        in_avals = [v.aval for v in eqn.invars]
        out_avals = [v.aval for v in eqn.outvars]
        est = spec.flops or (lambda i, o: jaxpr_flops(
            self._inner_jaxpr(eqn.params).jaxpr))
        attrs = {"atomic": eqn.params.get("name", "")}
        if spec.lower is not None:
            attrs["lower_hint"] = spec.lower
        self._emit(eqn, env, kind=spec.kind,
                   flops=float(est(in_avals, out_avals)), attrs=attrs)

    def _opaque(self, eqn, env, extra: dict | None = None) -> None:
        """Control-flow (or oversized/rolled scan) kept as one exact node."""
        bodies = _sub_jaxprs(eqn.params)
        flops = sum(jaxpr_flops(b) for b in bodies)
        if eqn.primitive.name == "scan":
            flops *= max(int(eqn.params.get("length", 1)), 1)
        kind = "elementwise"
        if any(e.primitive.name == "dot_general" for b in bodies
               for e in b.eqns):
            kind = "matmul"
        self._emit(eqn, env, kind=kind, flops=flops, attrs=extra)

    # -- scan unrolling ----------------------------------------------------
    def _scan(self, eqn, env) -> None:
        p = eqn.params
        body: jex_core.ClosedJaxpr = p["jaxpr"]
        length = int(p["length"])
        if self.roll_scans and length > 1:
            # A `lax.scan` is body-invariant BY CONSTRUCTION (one jaxpr, one
            # carry/slice signature for every trip) -- models whose layers
            # differ structurally can only be written as Python loops, which
            # arrive pre-unrolled.  Keep it rolled: ONE looped node binding
            # the scan primitive exactly, lowered once, so trace time and
            # graph size stay O(1) in the layer/microbatch count.
            self._opaque(eqn, env,
                         extra={"rolled_scan": True, "length": length})
            return
        if (length < 1
                or length * max(len(body.jaxpr.eqns), 1) > self.max_unroll_eqns):
            self._opaque(eqn, env)
            return
        nc, ncar = int(p["num_consts"]), int(p["num_carry"])
        reverse = bool(p.get("reverse", False))
        in_names = self._materialize(eqn, env)
        const_names = in_names[:nc]
        carry = in_names[nc:nc + ncar]
        xs = in_names[nc + ncar:]
        body_consts = [self.add_const(c) for c in body.consts]
        n_ys = len(eqn.outvars) - ncar
        ys: list[list[str | None]] = [[None] * length for _ in range(n_ys)]
        steps = range(length - 1, -1, -1) if reverse else range(length)
        for t in steps:
            x_t = [self._index(nm, t, body.jaxpr.invars[nc + ncar + j].aval)
                   for j, nm in enumerate(xs)]
            outs = self.run_jaxpr(body.jaxpr, body_consts,
                                  const_names + carry + x_t)
            carry = outs[:ncar]
            for j, y in enumerate(outs[ncar:]):
                ys[j][t] = y
        out_names = carry + [self._stack(parts, eqn.outvars[ncar + j].aval)
                             for j, parts in enumerate(ys)]
        for var, nm in zip(eqn.outvars, out_names):
            if not _is_dropvar(var):
                env[var] = nm

    def _index(self, src: str, t: int, aval) -> str:
        node = self.g.add(Node(
            self.fresh(f"{src}.t{t}"), "reshape", [src], _spec(aval),
            0.0, 0.0, {"prim": "index", "params": f"t={t}",
                       "_eval": (lambda a, _t=t: jax.lax.index_in_dim(
                           a, _t, axis=0, keepdims=False))}))
        return node.name

    def _stack(self, parts: list[str], aval) -> str:
        node = self.g.add(Node(
            self.fresh("stack"), "concat", list(parts), _spec(aval),
            0.0, 0.0, {"prim": "stack", "params": "axis=0", "axis": 0,
                       "_eval": (lambda *xs: jnp.stack(xs, axis=0))}))
        return node.name


def trace(fn: Callable, *example_args, name: str | None = None,
          max_unroll_eqns: int = MAX_UNROLL_EQNS,
          roll_scans: bool = False) -> TracedFunction:
    """Import `fn` (traced on `example_args`) into a Graph.

    The example args may be any pytrees of arrays; subsequent executions of
    the traced artifact must pass the same structure (same shapes => cached
    executables, zero new lowerings).  `roll_scans` keeps every multi-trip
    `lax.scan` as ONE looped node (tagged `attrs["rolled_scan"]`) instead of
    unrolling -- numerically exact, lowered once, O(1) trace in the trip
    count, at the price of hiding the body from sf-node selection."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    flat, in_tree = jax.tree_util.tree_flatten(example_args)
    imp = _Importer(name or getattr(fn, "__name__", "traced") or "traced",
                    max_unroll_eqns, roll_scans)
    in_names = []
    for i, (var, val) in enumerate(zip(closed.jaxpr.invars, flat)):
        nm = f"arg{i}"
        imp.g.input(nm, tuple(var.aval.shape), str(var.aval.dtype))
        in_names.append(nm)
    const_names = [imp.add_const(c) for c in closed.consts]
    out_refs = imp.run_jaxpr(closed.jaxpr, const_names, in_names)
    flat_out, out_tree = jax.tree_util.tree_flatten(out_shape)
    out_names = []
    for i, ref in enumerate(out_refs):
        out_names.append(imp.g.output(f"out{i}", ref).name)
    if len(flat_out) != len(out_names):
        raise AssertionError("output arity mismatch between jaxpr and pytree")
    return TracedFunction(imp.g, imp.consts, in_names, in_tree, out_names,
                          out_tree, closed)
