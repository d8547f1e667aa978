"""Executor backends: run an operator Graph in bsp / vertical / kitsune mode.

Three backends behind one ABC (the vLLM ExecutorBase idiom):

  * BSPBackend      -- jits every node separately (one kernel per op, every
    intermediate round-trips through HBM; the PyTorch-eager baseline).
  * VerticalBackend -- lowers the WHOLE graph as one program (the
    TensorRT/AStitch-style vertical-fusion baseline: one launch, XLA fuses
    temporally, intermediates spill once per-unit tiles exceed on-chip
    capacity).
  * KitsuneBackend  -- lowers every sf-node as ONE fused program
    (spatial-dataflow mode); ops outside sf-nodes fall back to per-op BSP.
    With a `lower_kernels` plan (core/lower.py) the fused programs call the
    REAL Pallas dataflow kernels for matched stage chains (fused MLP /
    SwiGLU, flash attention/decode, queue_reduce) instead of replaying the
    member ops' jnp closures.

Numerical equivalence between the three modes is a test invariant; the
difference is *where the intermediates live*, which we measure from XLA's
`memory_analysis()` boundary bytes -- giving the Table-2 traffic-reduction
numbers from the real compiler rather than a model.

Compiled executables are cached process-wide in `executable_cache()`, keyed
by (graph fingerprint / backend key, program name, feed shapes+dtypes), so a
second run with same-shaped feeds performs ZERO new lowerings (observable
via `lowering_count()`).  This is the hot-path contract the serving stack
relies on: `GraphExecutor.run` no longer re-jits every node on every call.

Execution itself is driven by per-shape ExecutionPlans: the first run per
feed/param shape signature resolves every value name to an integer slot,
binds the cached executables directly, and decides which dead intermediates
to donate; steady-state `Engine.run` is then a tight loop over prebound
executables, each launch under a `program` span (repro.spans).  The legacy
dict-driven loop stays as `Engine.run_legacy`, the plan's differential
oracle.
"""
from __future__ import annotations

import abc
import functools
import threading
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..spans import span
from .graph import Graph, Node, graph_fingerprint, subgraph_interface
from .patterns import Selection, select_subgraphs

_EW_FNS: dict[str, Callable] = {
    "add": lambda *xs: functools.reduce(jnp.add, xs),
    "mul": lambda *xs: functools.reduce(jnp.multiply, xs),
    "relu": lambda x: jnp.maximum(x, 0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "identity": lambda x: x,
}


def init_params(graph: Graph, key: jax.Array, scale: float = 0.02,
                dtype=jnp.float32) -> dict[str, Any]:
    """Materialize weights for linear/norm/gather nodes."""
    params: dict[str, Any] = {}
    for n in graph.topo():
        if "_eval" in n.attrs:
            continue  # traced node: weights arrive as captured consts
        key, sub = jax.random.split(key)
        if n.kind == "linear":
            d_in, d_out = n.attrs["d_in"], n.attrs["d_out"]
            params[n.name] = {"w": jax.random.normal(sub, (d_in, d_out), dtype) * scale}
            if n.attrs.get("bias"):
                params[n.name]["b"] = jnp.zeros((d_out,), dtype)
        elif n.kind == "norm":
            params[n.name] = {"g": jnp.ones((n.out.shape[-1],), dtype)}
        elif n.kind == "gather":
            params[n.name] = {"table": jax.random.normal(sub, n.attrs["table"], dtype) * scale}
    return params


def _eval_node(n: Node, inputs: list[jax.Array], p: dict | None) -> jax.Array:
    if n.kind in ("input", "const"):
        raise AssertionError("inputs are fed externally")
    ev = n.attrs.get("_eval")
    if ev is not None:
        # traced node (core/trace.py): the closure binds the exact jax
        # primitive + params, so semantics match the source jaxpr bit-for-bit
        return ev(*inputs)
    if n.kind == "linear":
        y = inputs[0] @ p["w"]
        if n.attrs.get("bias"):
            y = y + p["b"]
        return y
    if n.kind == "matmul":
        b = inputs[1]
        if n.attrs.get("transpose_b"):
            b = jnp.swapaxes(b, -1, -2)
        return inputs[0] @ b
    if n.kind == "elementwise":
        return _EW_FNS[n.attrs.get("fn", "add")](*inputs)
    if n.kind == "norm":
        x = inputs[0]
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * p["g"]
    if n.kind == "softmax":
        return jax.nn.softmax(inputs[0], axis=-1)
    if n.kind == "attention":
        q, k, v = inputs
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
        logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
        if n.attrs.get("causal", True):
            s, t = logits.shape[-2], logits.shape[-1]
            mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
            logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v)
    if n.kind == "reduce":
        return jnp.sum(inputs[0], axis=n.attrs["axis"],
                       keepdims=n.attrs.get("keepdims", False))
    if n.kind == "reduce_partial":
        # fan-in stage: partial sums over `fanin` chunks of the reduce axis
        x = inputs[0]
        axis = n.attrs["axis"] % x.ndim
        fanin = n.attrs["fanin"]
        size = x.shape[axis]
        pad = (-size) % fanin
        if pad:
            padw = [(0, 0)] * x.ndim
            padw[axis] = (0, pad)
            x = jnp.pad(x, padw)
        x = jnp.moveaxis(x, axis, 0)
        x = x.reshape((fanin, -1) + x.shape[1:])
        return jnp.sum(x, axis=1)  # (fanin, *rest)
    if n.kind == "reduce_final":
        return jnp.sum(inputs[0], axis=0)
    if n.kind == "gather":
        return p["table"][inputs[0]]
    if n.kind == "concat":
        return jnp.concatenate(inputs, axis=n.attrs.get("axis", -1))
    if n.kind == "reshape":
        return inputs[0].reshape(n.out.shape)
    if n.kind == "output":
        return inputs[0]
    raise NotImplementedError(n.kind)


# ---------------------------------------------------------------------------
# Process-wide executable cache + lowering counter
# ---------------------------------------------------------------------------

_LOWERINGS = 0


def lowering_count() -> int:
    """Monotonic count of fresh XLA lowerings/compiles this process has done.

    Tests assert that a second `CompiledApp.run()` with same-shaped feeds
    leaves this unchanged."""
    return _LOWERINGS


def _note_lowering() -> None:
    global _LOWERINGS
    _LOWERINGS += 1


class ExecutableCache:
    """Shape-keyed store of compiled XLA executables (plus their traffic
    stats).  One process-wide instance backs every CompiledApp/GraphExecutor;
    `get_or_build` counts a lowering on every miss.

    Thread-safe: the serve engine shares this one cache across instances
    (and request threads), so `get_or_build` holds a lock for the whole
    check-build-insert -- at most one build per key, ever.  Accepted
    tradeoff: a thread hitting a DIFFERENT key blocks while a build is in
    flight; builds happen once per (program, shape) lifetime, hits are the
    steady state, and the ExecutionPlan fast path does not touch the cache
    at all.  `capacity` optionally bounds the store with LRU eviction
    (`evictions` in stats); the default None preserves the historical
    unbounded behavior."""

    def __init__(self, capacity: int | None = None):
        self._store: OrderedDict[Any, Any] = OrderedDict()
        self._lock = threading.RLock()
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self):
        with self._lock:
            return len(self._store)

    def __contains__(self, key):
        with self._lock:
            return key in self._store

    def get(self, key):
        """Passive lookup (introspection/tests): no LRU touch, no counters."""
        with self._lock:
            return self._store.get(key)

    def keys(self):
        with self._lock:
            return list(self._store)

    def get_or_build(self, key, build: Callable[[], Any]):
        with self._lock:
            hit = self._store.get(key)
            if hit is not None:
                self.hits += 1
                self._store.move_to_end(key)
                return hit
            self.misses += 1
            val = build()
            _note_lowering()
            self._store[key] = val
            self._evict()
            return val

    def set_capacity(self, capacity: int | None) -> None:
        with self._lock:
            self.capacity = capacity
            self._evict()

    def _evict(self) -> None:
        if self.capacity is None:
            return
        while len(self._store) > max(self.capacity, 1):
            self._store.popitem(last=False)
            self.evictions += 1

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"size": len(self._store), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "capacity": self.capacity}

    def clear(self):
        with self._lock:
            self._store.clear()


_CACHE = ExecutableCache()


def executable_cache() -> ExecutableCache:
    return _CACHE


def clear_executable_cache() -> None:
    _CACHE.clear()


class VerdictCache:
    """Process-wide store of kernel-lowering profitability verdicts
    (core/lower.py), living alongside the executable cache so repeat
    compiles of the same (kernel pattern, shape, dtype, hw) site pay
    neither the roofline estimate nor the one-shot microbenchmark again.

    Deliberately NOT an ExecutableCache: `get_or_build` there counts an XLA
    lowering on every miss, and tests pin `lowering_count()` stability --
    verdicts are compile-time decisions, not compiled programs."""

    def __init__(self):
        self._store: dict[Any, Any] = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def __len__(self):
        with self._lock:
            return len(self._store)

    def __contains__(self, key):
        with self._lock:
            return key in self._store

    def get(self, key):
        with self._lock:
            v = self._store.get(key)
            if v is None:
                self.misses += 1
            else:
                self.hits += 1
            return v

    def put(self, key, verdict) -> None:
        with self._lock:
            self._store[key] = verdict

    def items(self) -> list[tuple[Any, Any]]:
        """(key, verdict) of every decided site."""
        with self._lock:
            return list(self._store.items())

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"size": len(self._store), "hits": self.hits,
                    "misses": self.misses}

    def clear(self) -> None:
        with self._lock:
            self._store.clear()


_VERDICTS = VerdictCache()


def verdict_cache() -> VerdictCache:
    return _VERDICTS


def clear_verdict_cache() -> None:
    _VERDICTS.clear()


def _shape_key(tree) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),) + tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", type(l).__name__)))
        for l in leaves)


# ---------------------------------------------------------------------------
# Programs and backends
# ---------------------------------------------------------------------------

@dataclass
class Program:
    """One lowerable unit: a callable over (feed, params) dicts.

    fn=None marks a zero-cost op (reshape/output outside any sf-node) that is
    evaluated inline without a kernel launch.  `outs` is the static order of
    the result dict's keys -- the ExecutionPlan binds them to integer slots
    once instead of walking dict results per call."""
    name: str
    needs: tuple[str, ...]                # graph values consumed
    params: tuple[str, ...] = ()          # param keys consumed
    fn: Callable | None = None            # (feed, params) -> {name: value}
    node: Node | None = None              # set for inline (free) programs
    outs: tuple[str, ...] = ()            # value names produced, in order


@dataclass
class _Executable:
    compiled: Any
    bytes_accessed: float
    temp_bytes: float
    # donation telemetry: ((arg name, nbytes, is_declared_feed), ...) for the
    # positions jit was ASKED to donate, XLA's measured alias bytes for the
    # whole executable, and whether XLA warned that some donation was unusable
    donation: tuple = ()
    aliased_bytes: float = 0.0
    donation_declined: bool = False

    @property
    def donated_bytes(self) -> float:
        return float(sum(nb for _, nb, _ in self.donation))


def _traffic(compiled) -> tuple[float, float]:
    """HBM boundary traffic of one program: arguments + outputs.

    Per-op (BSP) programs: this is exactly the op's DRAM traffic.  Fused
    (Kitsune/vertical) programs: intermediates between member ops are
    internal -- on TPU the dataflow kernels keep them in VMEM, so boundary
    bytes are the true HBM traffic; XLA temp bytes are reported separately."""
    m = compiled.memory_analysis()
    return (float(m.argument_size_in_bytes + m.output_size_in_bytes),
            float(m.temp_size_in_bytes))


def _op_program(g: Graph, node: Node) -> Program:
    def fn(feed: dict[str, jax.Array], params: dict, _n=node) -> dict:
        ins = [feed[i] for i in _n.inputs]
        return {_n.name: _eval_node(_n, ins, params.get(_n.name))}

    return Program(node.name, tuple(node.inputs), (node.name,), fn,
                   outs=(node.name,))


def _free_program(node: Node) -> Program:
    return Program(node.name, tuple(node.inputs), (), None, node,
                   outs=(node.name,))


def _sf_program(g: Graph, name: str, members: list[str],
                matches: Iterable | None = None) -> Program:
    """Fused program for one sf-node.

    `matches` (KernelMatch objects from core/lower.py, duck-typed: `.ops`,
    `.out`, `.call(vals, params)`) replace runs of member ops with real
    Pallas kernel calls; the members they cover are skipped by the jnp
    interpretation loop and their internal intermediates never materialize.
    Without matches the program replays every member's jnp closure (the
    pre-lowering vertical-fusion-per-sf-node behavior)."""
    pkeys = tuple(members)
    match_of: dict[str, Any] = {}
    for km in (matches or ()):
        for o in km.ops:
            match_of[o] = km
    # static schedule: member ops in topo order, each match emitted once at
    # its first member's position (all kernel inputs are available there)
    schedule: list[tuple[bool, Any]] = []
    emitted: set[int] = set()
    for m in members:
        km = match_of.get(m)
        if km is not None:
            if id(km) not in emitted:
                schedule.append((True, km))
                emitted.add(id(km))
            continue
        schedule.append((False, g.nodes[m]))
    # needs/exports come from the SHARED interface helper (core/graph.py):
    # exports are values consumed outside the sf-node (queue payloads stay
    # on-chip); match internals are single-consumer-internal by matcher
    # contract, so they are never exports.  program_struct_key hashes this
    # same derivation, so struct-equal programs share a calling convention.
    internal = {o for km in (matches or ()) for o in km.ops if o != km.out}
    need, exports = subgraph_interface(g, members, internal)

    def fn(feed: dict[str, jax.Array], params: dict) -> dict:
        vals = dict(feed)
        for is_kernel, item in schedule:
            if is_kernel:
                vals[item.out] = item.call(vals, params)
            else:
                ins = [vals[i] for i in item.inputs]
                vals[item.name] = _eval_node(item, ins, params.get(item.name))
        return {m: vals[m] for m in exports}

    return Program(name, need, pkeys, fn, outs=exports)


class ExecutorBackend(abc.ABC):
    """Plans a Graph into an ordered list of lowerable Programs."""

    mode: str = "?"

    def __init__(self, graph: Graph):
        self.graph = graph

    @abc.abstractmethod
    def plan(self) -> list[Program]:
        ...

    def key(self) -> tuple:
        """Cache-key component distinguishing this backend's programs."""
        return (self.mode,)


class BSPBackend(ExecutorBackend):
    """One kernel per op; free ops (reshape/output) evaluated inline."""

    mode = "bsp"

    def plan(self) -> list[Program]:
        progs = []
        for n in self.graph.topo():
            if n.kind in ("input", "const"):
                continue
            progs.append(_free_program(n) if n.is_free else
                         _op_program(self.graph, n))
        return progs


class VerticalBackend(ExecutorBackend):
    """Whole-graph single-program fusion: the vertical-fusion baseline."""

    mode = "vertical"

    def plan(self) -> list[Program]:
        g = self.graph
        inputs = tuple(n.name for n in g.topo() if n.kind in ("input", "const"))
        pkeys = tuple(n.name for n in g.topo()
                      if n.kind in ("linear", "norm", "gather"))
        outs = [n for n in g.topo() if n.kind == "output"]
        if outs:
            exports = {n.name: n.inputs[0] for n in outs}
        else:  # fall back: leaves
            succ = g.successors_map()
            exports = {k: k for k in g.nodes
                       if not succ.get(k) and g.nodes[k].kind not in ("input", "const")}

        def fn(feed: dict[str, jax.Array], params: dict) -> dict:
            vals = dict(feed)
            for n in g.topo():
                if n.name in vals:
                    continue
                ins = [vals[i] for i in n.inputs]
                vals[n.name] = _eval_node(n, ins, params.get(n.name))
            return {name: vals[src] for name, src in exports.items()}

        return [Program(f"{g.name}.vertical", inputs, pkeys, fn,
                        outs=tuple(exports))]


class KitsuneBackend(ExecutorBackend):
    """sf-nodes as single fused programs; everything else per-op BSP.

    `lowering` (a core/lower.py LoweringPlan, or None) maps sf-node member
    chains onto real Pallas kernels inside the fused programs."""

    mode = "kitsune"

    def __init__(self, graph: Graph, sf_members: Iterable[tuple[str, list[str]]],
                 lowering=None):
        super().__init__(graph)
        self.sf_members = [(name, list(members)) for name, members in sf_members]
        self.lowering = lowering

    def key(self) -> tuple:
        low_sig = self.lowering.signature() if self.lowering is not None else ()
        return (self.mode,
                tuple((n, tuple(m)) for n, m in self.sf_members),
                low_sig)

    def plan(self) -> list[Program]:
        g = self.graph
        sf_of: dict[str, str] = {}
        members_of = dict(self.sf_members)
        for name, members in self.sf_members:
            for m in members:
                sf_of[m] = name
        progs: list[Program] = []
        emitted: set[str] = set()
        for n in g.topo():
            if n.kind in ("input", "const"):
                continue
            sf = sf_of.get(n.name)
            if sf is not None:
                if sf not in emitted:
                    matches = (self.lowering.matches_for(sf)
                               if self.lowering is not None else None)
                    progs.append(_sf_program(g, sf, members_of[sf], matches))
                    emitted.add(sf)
                continue
            progs.append(_free_program(n) if n.is_free else
                         _op_program(g, n))
        return progs


def make_backend(mode: str, graph: Graph,
                 sf_members: Iterable[tuple[str, list[str]]] | None = None,
                 lowering=None) -> ExecutorBackend:
    if mode == "bsp":
        return BSPBackend(graph)
    if mode == "vertical":
        return VerticalBackend(graph)
    if mode == "kitsune":
        return KitsuneBackend(graph, sf_members or [], lowering)
    raise ValueError(f"unknown executor mode {mode!r}")


# ---------------------------------------------------------------------------
# Shared execution engine
# ---------------------------------------------------------------------------

@dataclass
class ExecutionReport:
    outputs: dict[str, jax.Array]
    bytes_accessed: float      # sum of program-boundary bytes (HBM traffic)
    n_programs: int            # kernels launched (BSP: one per op)
    temp_bytes: float = 0.0    # XLA temp allocations (on-chip residency proxy)
    # programs bound without a fresh lowering this call.  On the plan fast
    # path executables are PREBOUND, so hits == n_programs by definition and
    # executable_cache().stats() no longer advances per call.
    cache_hits: int = 0
    cache_misses: int = 0      # programs lowered+compiled fresh this call


def _plan_key(obj) -> tuple:
    """Cheap shape/dtype key over (nested dicts of) arrays -- ONE of these
    per run() call selects the ExecutionPlan, replacing the old per-program
    `_shape_key` (whose `str(treedef)` dominated dispatch time).  Dtypes are
    kept as np.dtype objects: they hash fine and `str(dtype)` alone costs
    tens of microseconds per call.  Dict items are sorted so key ORDER never
    splits plans (tree_flatten, which the legacy key used, sorts too)."""
    if isinstance(obj, dict):
        return tuple((k, _plan_key(v)) for k, v in sorted(obj.items()))
    if isinstance(obj, (list, tuple)):
        return (len(obj),) + tuple(_plan_key(v) for v in obj)
    shape = getattr(obj, "shape", None)
    if shape is not None:
        return (tuple(shape), obj.dtype)
    return (type(obj).__name__, repr(obj))


def _donation_supported() -> bool:
    """Whether this backend actually reuses donated buffers.  The plan
    computes donation decisions regardless (introspectable/testable); the
    decision is applied to jit only where the runtime honors it."""
    return jax.default_backend() in ("cpu", "tpu", "gpu")


@dataclass
class _StepSpec:
    """Shape-independent schedule entry (built once per Engine)."""
    prog: Program
    in_slots: tuple[int, ...]
    out_slots: tuple[int, ...]
    donate: tuple[int, ...]     # positions in prog.needs safe to donate
    release: tuple[int, ...]    # buffer slots dead after this step


@dataclass
class _FreeSpec:
    node: Node
    in_slots: tuple[int, ...]
    out_slot: int
    release: tuple[int, ...]


class _BoundStep:
    """One executable step of a compiled ExecutionPlan: the cached XLA
    executable plus prebound integer slots -- steady-state run() is a loop
    over these with no dict keying, no cache lookups, no shape hashing.
    Programs with no params are compiled WITHOUT the psub argument (an empty
    dict still costs a pytree flatten on every dispatch)."""
    __slots__ = ("call", "program", "in_slots", "out_slots", "pkeys",
                 "release", "donation")

    def __init__(self, exe, spec: _StepSpec, pkeys: tuple[str, ...]):
        self.call = exe.compiled
        self.program = spec.prog.name
        self.in_slots = spec.in_slots
        self.out_slots = spec.out_slots
        self.pkeys = pkeys
        self.release = spec.release
        # (donated entries, measured alias bytes, declined?) for telemetry
        self.donation = (exe.donation, exe.aliased_bytes,
                         exe.donation_declined)


def _compile_step(st) -> Callable:
    """Specialize one plan step into a closure `step(buf, params)` -- the
    steady-state loop is then one Python call per step with every slot,
    executable and release list already bound."""
    rel = st.release
    if type(st) is _FreeSpec:
        node, in_slots, out = st.node, st.in_slots, st.out_slot

        def step(buf, params):
            buf[out] = _eval_node(node, [buf[i] for i in in_slots], None)
            for r in rel:
                buf[r] = None
        return step
    call, in_slots, out_slots, pkeys = (st.call, st.in_slots, st.out_slots,
                                        st.pkeys)
    if not pkeys and len(in_slots) == 1 and len(out_slots) == 1:
        i0, o0 = in_slots[0], out_slots[0]

        def step(buf, params):
            buf[o0] = call(buf[i0])[0]
            for r in rel:
                buf[r] = None
        return step
    if not pkeys:
        def step(buf, params):
            outs = call(*[buf[i] for i in in_slots])
            for o, v in zip(out_slots, outs):
                buf[o] = v
            for r in rel:
                buf[r] = None
        return step

    def step(buf, params):
        outs = call({k: params[k] for k in pkeys}, *[buf[i] for i in in_slots])
        for o, v in zip(out_slots, outs):
            buf[o] = v
        for r in rel:
            buf[r] = None
    return step


def _spanned(fn: Callable, name: str, **args) -> Callable:
    """`fn` run under one span, bound once per plan step so the hot loop
    looks nothing up for it."""
    def step(buf, params):
        with span(name, **args):
            fn(buf, params)
    return step


def _plan_step(st) -> Callable:
    """A plan step's closure under its span: `program` for an executable
    launch, `inline` for a free op that dispatches device work.  Output
    entries are identities and run bare."""
    fn = _compile_step(st)
    if type(st) is not _FreeSpec:
        return _spanned(fn, "program", program=st.program)
    if st.node.kind == "output":
        return fn
    return _spanned(fn, "inline", op=st.node.kind)


class ExecutionPlan:
    """Everything `run()` needs for one (feed, param) shape signature:
    prebound executables, slot wiring, and precomputed traffic totals.
    `steps` keeps the bound step objects for introspection; `fns` are the
    specialized closures the hot loop actually runs."""
    __slots__ = ("steps", "fns", "bytes_accessed", "temp_bytes",
                 "n_programs", "donation")

    def __init__(self, steps, bytes_accessed, temp_bytes, n_programs):
        self.steps = steps
        self.fns = tuple(_plan_step(st) for st in steps)
        self.bytes_accessed = bytes_accessed
        self.temp_bytes = temp_bytes
        self.n_programs = n_programs
        self.donation = self._donation_summary(steps)

    @staticmethod
    def _donation_summary(steps) -> dict:
        """Aggregate per-executable donation telemetry for this plan: which
        values (and in particular which DECLARED feeds) were donated, how
        many bytes XLA actually aliased in place, and whether any donation
        was declined (saved bytes = aliased bytes: each one is a buffer the
        program reused instead of allocating fresh)."""
        feeds: dict[str, dict] = {}
        donated = aliased = 0.0
        declined = False
        for st in steps:
            info = getattr(st, "donation", None)
            if not info:
                continue
            entries, alias_bytes, was_declined = info
            step_donated = float(sum(nb for _, nb, _ in entries))
            donated += step_donated
            aliased += alias_bytes
            declined |= was_declined and bool(entries)
            ok = not was_declined and alias_bytes >= step_donated > 0
            for name, nb, is_feed in entries:
                if not is_feed:
                    continue
                e = feeds.setdefault(name, {"nbytes": 0, "aliased": True})
                e["nbytes"] += nb
                e["aliased"] &= ok
        return {"donated_bytes": donated, "aliased_bytes": aliased,
                "bytes_saved": min(aliased, donated) if donated else 0.0,
                "declined": declined, "feeds": feeds}


class Engine:
    """Runs a backend's program list against the process-wide executable
    cache.  `engine_key` namespaces cache entries (graph fingerprint +
    backend/options signature), so identical graphs share executables across
    Engine instances.

    Execution is plan-based: the first `run()` per (feed, param) shape
    signature compiles an ExecutionPlan -- feed/param names resolved to
    integer slots, cache keys and shape keys built once, executables bound
    directly, intermediates in a flat buffer list, and arguments donated
    where the value has no later consumer.  Steady-state `run()` is then a
    loop over prebound executables with near-zero Python overhead
    (`run_legacy` keeps the historical dict-driven loop as the differential
    oracle)."""

    # plans an engine keeps live; beyond this the least-recent shape's plan
    # (and its pinned executable refs) is dropped and rebuilt on next use
    MAX_PLANS = 64

    def __init__(self, backend: ExecutorBackend, engine_key: tuple,
                 cache: ExecutableCache | None = None,
                 donate_feeds: frozenset[str] | set[str] = frozenset(),
                 struct_keys: dict[str, str] | None = None):
        self.backend = backend
        self.graph = backend.graph
        self.programs = backend.plan()
        self.donate_feeds = frozenset(donate_feeds)
        # program name -> canonical structural key (core/graph.py
        # program_struct_key), provided by the dedupe pass.  Param-less
        # programs carrying a struct key are cached under it INSTEAD of the
        # engine-namespaced name key, so N structurally equal stages (and
        # identical stages of other engines) bind to ONE executable.
        self.struct_keys = dict(struct_keys or {})
        self.engine_key = (engine_key,) + backend.key()
        if self.donate_feeds:
            # donating engines must never share executables with
            # non-donating ones (the donated parameter positions differ)
            self.engine_key += (("donate",) + tuple(sorted(self.donate_feeds)),)
        self.cache = cache if cache is not None else _CACHE
        self._plans: OrderedDict[tuple, ExecutionPlan] = OrderedDict()
        self._build_skeleton()

    # -- shape-independent schedule (once per Engine) ----------------------
    def _build_skeleton(self) -> None:
        g = self.graph
        slots: dict[str, int] = {}

        def slot(name: str) -> int:
            return slots.setdefault(name, len(slots))

        self._feed_slots = tuple(
            (slot(n.name), n.name) for n in g.topo()
            if n.kind in ("input", "const"))
        feed_names = {name for _, name in self._feed_slots}
        # run outputs: output nodes, else leaves (historical contract --
        # unconsumed feeds count as leaves, matching the legacy vals dict)
        out_nodes = [n.name for n in g.topo() if n.kind == "output"]
        if out_nodes:
            run_outs = list(out_nodes)
        else:
            succ = g.successors_map()
            run_outs = [n.name for n in g.topo() if not succ.get(n.name)]
        # last reader of every value (END for run outputs)
        END = len(self.programs)
        last_use: dict[str, int] = {}
        read_by_free: set[str] = set()
        exe_produced: set[str] = set()
        for idx, prog in enumerate(self.programs):
            for nm in prog.needs:
                last_use[nm] = idx
            if prog.fn is None:
                read_by_free.update(prog.needs)
        for name in run_outs:
            last_use[name] = END
        steps: list[Any] = []
        for idx, prog in enumerate(self.programs):
            in_slots = tuple(slot(nm) for nm in prog.needs)
            release = tuple(slots[nm] for nm in prog.needs
                            if last_use.get(nm) == idx)
            if prog.fn is None:
                steps.append(_FreeSpec(prog.node, in_slots,
                                       slot(prog.node.name), release))
                continue
            # donate a position iff the value dies here, was produced by an
            # earlier executable (fresh XLA buffer -- feeds/consts belong to
            # the caller, free-op results may be views) OR is a feed the
            # caller DECLARED donatable (donate_feeds: training threads
            # optimizer/param state in place this way), no free op ever
            # reads it (views would share the donated buffer), and the name
            # is not passed at two positions (duplicated inputs like
            # mul(a, a) would donate one buffer twice)
            donate = tuple(
                p for p, nm in enumerate(prog.needs)
                if (last_use.get(nm) == idx
                    and ((nm in exe_produced and nm not in feed_names)
                         or (nm in self.donate_feeds and nm in feed_names))
                    and nm not in read_by_free
                    and prog.needs.count(nm) == 1))
            out_slots = tuple(slot(nm) for nm in prog.outs)
            steps.append(_StepSpec(prog, in_slots, out_slots, donate, release))
            exe_produced.update(prog.outs)
        self._steps = steps
        self._run_out_slots = tuple((name, slots[name]) for name in run_outs)
        self._n_slots = len(slots)

    # -- execution ---------------------------------------------------------
    def run(self, feeds: dict[str, jax.Array], params: dict,
            measure: bool = True) -> ExecutionReport:
        """Execute via the per-shape ExecutionPlan.  The first call per
        shape signature builds the plan (lowering at most once per shape,
        via the process-wide cache); later calls replay the prebound
        executables.  measure=False only zeroes the traffic/program
        accounting, matching the historical GraphExecutor contract."""
        key, buf = self.feed(feeds, params)
        return self.launch(key, buf, feeds, params, measure)

    def feed(self, feeds: dict[str, jax.Array], params: dict,
             ) -> tuple[tuple, list]:
        """One call's plan key and its value buffer, feeds in their
        slots (the first half of `run`)."""
        buf: list[Any] = [None] * self._n_slots
        for s, name in self._feed_slots:
            if name not in feeds:
                raise KeyError(f"missing feed for {name}")
            buf[s] = feeds[name]
        return (_plan_key(feeds), _plan_key(params)), buf

    def launch(self, key: tuple, buf: list, feeds: dict[str, jax.Array],
               params: dict, measure: bool = True) -> ExecutionReport:
        """Run the plan for `key` over a buffer from `feed` (the second
        half of `run`); the first call per key builds the plan."""
        plan = self._plans.get(key)
        if plan is None:
            return self._build_and_run(key, buf, feeds, params, measure)
        self._plans.move_to_end(key)
        for step in plan.fns:
            step(buf, params)
        outs = {name: buf[s] for name, s in self._run_out_slots}
        if not measure:
            return ExecutionReport(outs, 0.0, 0, 0.0, plan.n_programs, 0)
        return ExecutionReport(outs, plan.bytes_accessed, plan.n_programs,
                               plan.temp_bytes, plan.n_programs, 0)

    def _build_and_run(self, key: tuple, buf: list, feeds: dict,
                       params: dict, measure: bool) -> ExecutionReport:
        """First call per shape signature: execute while binding the plan."""
        bound: list[Any] = []
        total_bytes = total_temp = 0.0
        n_programs = hits = misses = 0
        donate_ok = _donation_supported()
        # feed buffers aliased under TWO names (e.g. tied state leaves) are
        # never donated: donating one name invalidates the other's reads
        donated_ids: set[int] = set()
        if self.donate_feeds:
            seen_ids: set[int] = set()
            for _, name in self._feed_slots:
                i = id(feeds[name])
                (donated_ids if i in seen_ids else seen_ids).add(i)
        for spec in self._steps:
            if type(spec) is _FreeSpec:
                ins = [buf[i] for i in spec.in_slots]
                if spec.node.kind == "output":
                    buf[spec.out_slot] = _eval_node(spec.node, ins, None)
                else:
                    with span("inline", op=spec.node.kind):
                        buf[spec.out_slot] = _eval_node(spec.node, ins, None)
                bound.append(spec)
            else:
                prog = spec.prog
                pkeys = tuple(k for k in prog.params if k in params)
                psub = {k: params[k] for k in pkeys}
                ins = tuple(buf[i] for i in spec.in_slots)
                donate = spec.donate if donate_ok else ()
                if donate and self.donate_feeds:
                    # two DECLARED feed names may alias ONE buffer (e.g.
                    # tied state leaves): donating it at both positions is
                    # an XLA runtime error, so only the first position seen
                    # this call keeps its donation.  The check covers feed
                    # buffers only -- the feeds dict keeps them alive for
                    # the whole call, so their ids are stable (intermediate
                    # buffers are released mid-run and id() reuse would make
                    # the decision, and the cache keys, nondeterministic).
                    # The plan bakes this in; later calls must alias at most
                    # as much as the plan-building call (feeding each call
                    # the previous call's outputs satisfies this).
                    keep = []
                    for p in donate:
                        if prog.needs[p] in self.donate_feeds:
                            i = id(ins[p])
                            if i in donated_ids:
                                continue
                            donated_ids.add(i)
                        keep.append(p)
                    donate = tuple(keep)
                skey = self.struct_keys.get(prog.name) if not pkeys else None
                if skey is not None:
                    # canonical struct-keyed entry: NO engine namespace, so
                    # structurally equal programs share ONE executable across
                    # stages, apps, and engines.  Only safe for param-less
                    # programs (positional calling convention; name-keyed
                    # param dicts would split on pytree structure) -- traced
                    # apps always qualify.  Runtime shape/donation variation
                    # is still keyed (it changes the compiled artifact).
                    ckey = ("sfprog", skey, donate, _plan_key(ins))
                else:
                    ckey = self.engine_key + (
                        "plan", prog.name, donate,
                        _plan_key(ins), _plan_key(psub))
                before = self.cache.misses
                exe = self.cache.get_or_build(
                    ckey, lambda: self._build_positional(
                        prog, ins, psub, donate))
                if self.cache.misses > before:
                    misses += 1
                else:
                    hits += 1
                with span("program", program=prog.name):
                    outs = (exe.compiled(psub, *ins) if pkeys
                            else exe.compiled(*ins))
                st = _BoundStep(exe, spec, pkeys)
                for o, v in zip(st.out_slots, outs):
                    buf[o] = v
                total_bytes += exe.bytes_accessed
                total_temp += exe.temp_bytes
                n_programs += 1
                bound.append(st)
            for i in spec.release:
                buf[i] = None
        self._plans[key] = ExecutionPlan(bound, total_bytes, total_temp,
                                         n_programs)
        while len(self._plans) > self.MAX_PLANS:
            # bound per-engine plan memory: a dropped plan releases its
            # executable refs (the shared cache's own LRU can then evict)
            # and is transparently rebuilt from cache on next use
            self._plans.popitem(last=False)
        outs = {name: buf[s] for name, s in self._run_out_slots}
        if not measure:
            return ExecutionReport(outs, 0.0, 0, 0.0, hits, misses)
        return ExecutionReport(outs, total_bytes, n_programs, total_temp,
                               hits, misses)

    def _build_positional(self, prog: Program, ins: tuple, psub: dict,
                          donate: tuple[int, ...]) -> _Executable:
        """Lower and compile one program, or load it from JAX's cache.

        The program is jitted as `kitsune.<program name>`, which names its
        XLA module in a profiler trace.  A struct-keyed executable is built
        once per class, so it carries the name of the class's first
        program; names follow from the graph alone and are the same in
        every process."""
        if psub:
            def wrapped(psub_, *arrs):
                out = prog.fn(dict(zip(prog.needs, arrs)), psub_)
                return tuple(out[k] for k in prog.outs)
            args = (psub,) + ins
            shift = 1
        else:  # param-less program: drop the dict arg from the signature
            def wrapped(*arrs):
                out = prog.fn(dict(zip(prog.needs, arrs)), {})
                return tuple(out[k] for k in prog.outs)
            args = ins
            shift = 0
        wrapped.__name__ = wrapped.__qualname__ = f"kitsune.{prog.name}"
        jit_kw = {}
        if donate:
            jit_kw["donate_argnums"] = tuple(p + shift for p in donate)
        with warnings.catch_warnings(record=True) as caught, \
                span("compile_program", program=prog.name):
            # an unusable donation (XLA declined to alias, e.g. on CPU) is
            # only a missed reuse -- the dead buffer is freed either way.
            # RECORD instead of ignore: declined donations feed the telemetry
            # `Engine.donation_report()` / `CompiledApp.describe()` expose.
            warnings.simplefilter("always")
            compiled = jax.jit(wrapped, **jit_kw).lower(*args).compile()
        declined = any("donated buffers were not usable" in str(w.message)
                       for w in caught)
        for w in caught:  # replay anything unrelated to donation
            if "donated buffers were not usable" not in str(w.message):
                warnings.warn_explicit(w.message, w.category, w.filename,
                                       w.lineno)
        b, t = _traffic(compiled)
        info = tuple(
            (prog.needs[p],
             int(np.prod(ins[p].shape)) * ins[p].dtype.itemsize,
             prog.needs[p] in self.donate_feeds)
            for p in donate)
        try:
            aliased = float(getattr(compiled.memory_analysis(),
                                    "alias_size_in_bytes", 0.0) or 0.0)
        except Exception:
            aliased = 0.0
        return _Executable(compiled, b, t, donation=info,
                           aliased_bytes=aliased,
                           donation_declined=declined)

    def dedupe_stats(self) -> dict:
        """Structural-dedupe telemetry for this engine's program list.

        `n_classes` counts distinct (structural key, donation positions)
        pairs over the keyed programs: the number of executables a first run
        compiles for them (free programs never compile and unkeyed programs
        fall back to name-keyed entries).  Donation is part of the
        executable's ABI -- a class whose first copy consumes a live user
        feed while later copies consume dead intermediates splits into a
        non-donating and a donating variant (bounded: the handful of donate
        patterns, not the layer count), rather than silently downgrading the
        donating copies' in-place updates.  `hit_rate` is the fraction of
        keyed program instances served by another instance's executable --
        0.0 when every program is structurally unique, approaching 1.0 for
        deeply repeated layers."""
        progs = [p for p in self.programs if p.fn is not None]
        keyed = [self.struct_keys[p.name] for p in progs
                 if p.name in self.struct_keys]
        classes = {(self.struct_keys[st.prog.name], st.donate)
                   for st in self._steps if type(st) is _StepSpec
                   and st.prog.name in self.struct_keys}
        n_classes = len(classes) if classes else len(set(keyed))
        return {"n_programs": len(progs), "n_keyed": len(keyed),
                "n_classes": n_classes,
                "hit_rate": (1.0 - n_classes / len(keyed)) if keyed else 0.0}

    def donation_report(self) -> dict:
        """Donation telemetry across this engine's live ExecutionPlans:
        per-plan donated/aliased byte totals plus, for each DECLARED feed
        (donate_feeds), whether XLA actually aliased it in place.  On
        backends where donation is unsupported (or declined) the report
        shows donated > 0 with aliased == 0 -- the dead buffers were still
        freed, just not reused in place."""
        plans = []
        for plan in self._plans.values():
            d = plan.donation
            plans.append({"donated_bytes": d["donated_bytes"],
                          "aliased_bytes": d["aliased_bytes"],
                          "bytes_saved": d["bytes_saved"],
                          "declined": d["declined"],
                          "feeds": {k: dict(v) for k, v in d["feeds"].items()}})
        return {"declared_feeds": sorted(self.donate_feeds),
                "n_plans": len(plans),
                "plans": plans,
                "bytes_saved": sum(p["bytes_saved"] for p in plans)}

    # -- pre-plan reference loop (differential oracle) ---------------------
    def run_legacy(self, feeds: dict[str, jax.Array], params: dict,
                   measure: bool = True) -> ExecutionReport:
        """The historical dict-driven dispatch loop: per-program shape
        keying + cache lookups + dict feeds on EVERY call.  Numerically
        identical to `run()`; kept so tests can differential-check the plan
        runtime against it."""
        g = self.graph
        for n in g.topo():
            if n.kind in ("input", "const") and n.name not in feeds:
                raise KeyError(f"missing feed for {n.name}")
        vals: dict[str, jax.Array] = dict(feeds)
        total_bytes = total_temp = 0.0
        n_programs = hits = misses = 0
        for prog in self.programs:
            if prog.fn is None:  # reshape/output: zero-cost, not a launch
                ins = [vals[i] for i in prog.needs]
                vals[prog.node.name] = _eval_node(prog.node, ins, None)
                continue
            feed = {i: vals[i] for i in prog.needs}
            psub = {k: params[k] for k in prog.params if k in params}
            key = self.engine_key + (prog.name, _shape_key((feed, psub)))
            before = self.cache.misses
            exe = self.cache.get_or_build(
                key, lambda: self._build(prog, feed, psub))
            if self.cache.misses > before:
                misses += 1
            else:
                hits += 1
            vals.update(exe.compiled(feed, psub))
            if measure:
                total_bytes += exe.bytes_accessed
                total_temp += exe.temp_bytes
                n_programs += 1
        outs = {n.name: vals[n.name] for n in g.topo() if n.kind == "output"}
        if not outs:  # fall back: leaves
            succ = g.successors_map()
            outs = {k: v for k, v in vals.items() if not succ.get(k)}
        return ExecutionReport(outs, total_bytes, n_programs, total_temp,
                               hits, misses)

    @staticmethod
    def _build(prog: Program, feed: dict, psub: dict) -> _Executable:
        compiled = jax.jit(prog.fn).lower(feed, psub).compile()
        b, t = _traffic(compiled)
        return _Executable(compiled, b, t)


# ---------------------------------------------------------------------------
# Public executor API
# ---------------------------------------------------------------------------

class GraphExecutor:
    """Executes a Graph in 'bsp', 'vertical' or 'kitsune' mode on concrete
    arrays.  Thin compatibility wrapper over the backend/Engine split; prefer
    the `repro.compile()` front-door (core/compiler.py) for new code."""

    def __init__(self, graph: Graph, mode: str = "bsp",
                 selection: Selection | None = None):
        assert mode in ("bsp", "vertical", "kitsune")
        self.graph = graph
        self.mode = mode
        self.selection = selection or select_subgraphs(graph)
        self.covered = self.selection.covered if mode == "kitsune" else set()
        sf_members = [(sf.name, list(sf.members))
                      for sf in self.selection.sf_nodes]
        backend = make_backend(mode, graph, sf_members)
        self._engine = Engine(backend, (graph_fingerprint(graph),))

    def run(self, feeds: dict[str, jax.Array], params: dict,
            measure: bool = True) -> ExecutionReport:
        return self._engine.run(feeds, params, measure)


def compare_traffic(graph: Graph, feeds: dict[str, jax.Array],
                    params: dict) -> dict[str, float]:
    """Measured bytes-accessed: BSP vs Kitsune (Table-2 'Traffic Red.')."""
    bsp = GraphExecutor(graph, "bsp").run(feeds, params)
    kit = GraphExecutor(graph, "kitsune").run(feeds, params)
    for k in bsp.outputs:
        np.testing.assert_allclose(
            np.asarray(bsp.outputs[k], dtype=np.float32),
            np.asarray(kit.outputs[k], dtype=np.float32), rtol=2e-2, atol=2e-2)
    red = 1.0 - kit.bytes_accessed / max(bsp.bytes_accessed, 1.0)
    return {"bsp_bytes": bsp.bytes_accessed, "kitsune_bytes": kit.bytes_accessed,
            "traffic_reduction": red, "bsp_programs": bsp.n_programs,
            "kitsune_programs": kit.n_programs}
