"""The Kitsune compiler front-door: `repro.compile(graph, options)`.

This module turns the loose pipeline of free functions (select_subgraphs ->
design_pipeline -> balance -> GraphExecutor) into one staged, introspectable
compiler (the paper's SS5 end-to-end flow behind a single entrypoint):

    options = CompilerOptions(mode="kitsune")
    app = repro.compile(graph, options)      # runs the pass pipeline once
    report = app.run(feeds, params)          # cached executables; no re-jit

Pieces:

  * CompilerOptions -- every compiler knob in one frozen dataclass (mode,
    tile bytes, split-reduction threshold, pattern subset, balancing).
  * PassManager -- runs the stages as NAMED passes
    (`select -> split_reduction -> create_queues -> epilogue_fuse ->
    lower_kernels -> balance`) with per-pass wall-clock timing, an IR dump
    hook, support for reordering, and per-pass disabling (each disabled pass
    degrades to its identity/fallback form instead of crashing downstream
    passes).  `lower_kernels` (core/lower.py) pattern-matches the pipelined
    sf-node stages onto the real Pallas dataflow kernels (fused MLP /
    SwiGLU, flash attention/decode, queue_reduce), with per-op fallback
    reasons surfaced by `CompiledApp.describe()`.
  * CompiledApp -- the compiled artifact: selection + pipelined IR + balance
    results + an executor Engine whose XLA executables live in the
    process-wide cache keyed by (graph fingerprint, feed shapes, options),
    so repeated `run()` calls (and fresh `compile()`s of an identical graph)
    perform zero new lowerings.
  * cached_jit -- the same executable cache for arbitrary jax callables
    (used by serve/ and launch/ so the production launchers go through the
    compiler's caching layer instead of re-jitting per instance).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable

import jax

from ..spans import span
from .balance import BalanceResult, balance as _balance_pipeline
from .costmodel import GraphCost, HwSpec, device_hw, evaluate
from .executor import (Engine, ExecutionReport, _shape_key, executable_cache,
                       init_params, make_backend)
from .graph import Graph, graph_fingerprint
from .lower import LoweringPlan, lower_pipelines
from .patterns import PATTERN_LIBRARY, Selection, select_subgraphs
from .trace import TracedFunction, trace as trace_fn
from .pipeline import (DEFAULT_TILE_BYTES, SPLIT_REDUCTION_MIN, DedupeInfo,
                       OpQueue, Pipeline, PipelinedGraph, Stage,
                       dedupe_programs, fuse_epilogues, materialize_queues,
                       plan_queues, split_reductions)

MODES = ("bsp", "vertical", "kitsune")
PASS_NAMES = ("select", "split_reduction", "create_queues", "epilogue_fuse",
              "lower_kernels", "dedupe", "balance")


@dataclass(frozen=True)
class CompilerOptions:
    """Every knob of the compiler in one (hashable) place.

    mode                 executor mode the artifact runs in:
                         bsp      -- one kernel per op (eager baseline)
                         vertical -- whole graph as ONE program (vertical-
                                     fusion baseline)
                         kitsune  -- sf-nodes as fused dataflow programs
    tile_bytes           on-chip queue payload size (Algorithm 1)
    split_reduction_min  reductions at least this wide get fan-in/final split
    patterns             subset of PATTERN_LIBRARY names to match (None=all)
    min_sf_size          smallest op count an sf-node may have
    balance              run the ILP load-balancing pass (Algorithm 2)
    hw                   HwSpec the balance pass and estimate() default to
                         (None: `costmodel.device_hw()`, the attached TPU
                         host, or the modelled v5e-8 off-TPU)
    disable              pass names to skip (each falls back to its identity
                         form; e.g. disabling `epilogue_fuse` yields one
                         stage per op)
    lowering_policy      profitability gate on kernel matches (core/lower.py):
                         "always" force-lowers every match, "cost" decides by
                         roofline estimate alone, "auto" (default) settles
                         estimate-uncertain sites with a one-shot compile-time
                         microbenchmark (verdicts cached process-wide)
    roll_scans           callable path only: keep `lax.scan` loops as ONE
                         looped node instead of unrolling them -- the graph
                         (and trace time) stays O(1) in the layer/microbatch
                         count and the scan body lowers ONCE.  Off by
                         default: a rolled body is opaque to sf-node
                         selection and kernel lowering, so this is the
                         trace-scalability dial, not a general win
    dump_ir              hook called as dump_ir(pass_name, state) after every
                         pass -- the introspection point for IR dumps
    """
    mode: str = "kitsune"
    tile_bytes: int = DEFAULT_TILE_BYTES
    split_reduction_min: int = SPLIT_REDUCTION_MIN
    patterns: tuple[str, ...] | None = None
    min_sf_size: int = 2
    balance: bool = True
    hw: HwSpec | None = None
    disable: tuple[str, ...] = ()
    lowering_policy: str = "auto"
    roll_scans: bool = False
    dump_ir: Callable[[str, "CompileState"], None] | None = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.lowering_policy not in ("always", "cost", "auto"):
            raise ValueError(f"lowering_policy must be always|cost|auto, "
                             f"got {self.lowering_policy!r}")
        for p in self.disable:
            if p not in PASS_NAMES:
                raise ValueError(f"unknown pass {p!r} in disable "
                                 f"(known: {PASS_NAMES})")
        if self.patterns is not None:
            object.__setattr__(self, "patterns", tuple(self.patterns))
            for name in self.patterns:
                if name not in PATTERN_LIBRARY:
                    raise ValueError(f"unknown pattern {name!r} "
                                     f"(known: {tuple(PATTERN_LIBRARY)})")

    @property
    def disabled(self) -> frozenset[str]:
        dis = set(self.disable)
        if not self.balance:
            dis.add("balance")
        return frozenset(dis)

    def resolved_hw(self) -> HwSpec:
        return self.hw if self.hw is not None else device_hw()

    def cache_key(self) -> tuple:
        """Hashable identity for the executable cache (hooks excluded: they
        observe compilation but cannot change the produced programs)."""
        return (self.mode, self.tile_bytes, self.split_reduction_min,
                self.patterns, self.min_sf_size, tuple(sorted(self.disabled)),
                self.lowering_policy, self.roll_scans)


@dataclass
class CompileState:
    """Mutable state threaded through the pass pipeline."""
    graph: Graph
    selection: Selection | None = None
    work_graph: Graph | None = None                 # post split-reduction
    members_of: dict[str, list[str]] | None = None  # sf name -> members
    op_queues: dict[str, list[OpQueue]] = field(default_factory=dict)
    stages_of: dict[str, tuple[list[Stage], dict[str, Stage]]] = \
        field(default_factory=dict)
    pipelined: PipelinedGraph | None = None
    lowering: LoweringPlan | None = None            # lower_kernels artifact
    dedupe: DedupeInfo | None = None                # dedupe pass artifact
    balance_results: dict[str, BalanceResult] = field(default_factory=dict)


@dataclass
class PassRecord:
    """One pass of a compile; `seconds` is its `pass/<name>` span's
    (repro.spans)."""
    name: str
    seconds: float
    disabled: bool = False
    summary: str = ""


# -- pass bodies (and the identity fallbacks used when a pass is disabled) --

def _ensure_selection(state: CompileState, opts: CompilerOptions) -> Selection:
    if state.selection is None:
        state.selection = Selection(state.graph, [])
    return state.selection


def _ensure_work(state: CompileState, opts: CompilerOptions) -> Graph:
    if state.work_graph is None:
        sel = _ensure_selection(state, opts)
        state.work_graph = state.graph.clone()
        state.members_of = {sf.name: list(sf.members) for sf in sel.sf_nodes}
    return state.work_graph


def _invalidate_derived(state: CompileState) -> None:
    """Drop everything computed from a previous selection/work graph (pass
    reordering support: a structural pass re-running invalidates downstream
    state so lazy _ensure_* rebuilds it consistently)."""
    state.work_graph = None
    state.members_of = None
    state.op_queues = {}
    state.stages_of = {}
    state.pipelined = None
    state.lowering = None
    state.dedupe = None


def _pass_select(state: CompileState, opts: CompilerOptions) -> str:
    state.selection = select_subgraphs(state.graph, min_size=opts.min_sf_size,
                                       patterns=opts.patterns)
    _invalidate_derived(state)
    grouped, total = state.selection.coverage()
    return f"{len(state.selection.sf_nodes)} sf-nodes, coverage {grouped}/{total}"


def _skip_select(state: CompileState, opts: CompilerOptions) -> str:
    state.selection = Selection(state.graph, [])
    _invalidate_derived(state)
    return "selection disabled: 0 sf-nodes"


def _pass_split_reduction(state: CompileState, opts: CompilerOptions) -> str:
    sel = _ensure_selection(state, opts)
    work, members = split_reductions(sel, opts.split_reduction_min)
    # the rewrite renames member ops: stage/queue state built against the
    # old graph (reordered pipelines) is stale and must be rebuilt
    _invalidate_derived(state)
    state.work_graph, state.members_of = work, members
    n = sum(1 for node in state.work_graph.topo()
            if node.kind == "reduce_partial")
    return f"{n} reductions split"


def _skip_split_reduction(state: CompileState, opts: CompilerOptions) -> str:
    _ensure_work(state, opts)
    return "reductions left whole"


def _pass_create_queues(state: CompileState, opts: CompilerOptions) -> str:
    g = _ensure_work(state, opts)
    state.op_queues = {name: plan_queues(g, members)
                       for name, members in state.members_of.items()}
    n = sum(len(v) for v in state.op_queues.values())
    return f"{n} queue intents"


def _skip_create_queues(state: CompileState, opts: CompilerOptions) -> str:
    _ensure_work(state, opts)
    state.op_queues = {name: [] for name in state.members_of}
    return "no queues"


def _pass_epilogue_fuse(state: CompileState, opts: CompilerOptions,
                        enable: bool = True) -> str:
    g = _ensure_work(state, opts)
    state.stages_of = {
        name: fuse_epilogues(g, name, members, enable=enable)
        for name, members in state.members_of.items()}
    n_ops = sum(len(m) for m in state.members_of.values())
    n_stages = sum(len(s) for s, _ in state.stages_of.values())
    return f"{n_ops} ops -> {n_stages} stages"


def _skip_epilogue_fuse(state: CompileState, opts: CompilerOptions) -> str:
    return _pass_epilogue_fuse(state, opts, enable=False) + " (unfused)"


def _pipelined_members(pg: PipelinedGraph) -> dict[str, list[str]]:
    """Executable member list per pipeline: stage ops re-sorted to topo order
    (epilogue fusion can hoist an op into its producer's stage past
    siblings).  This is the exact member order the kitsune backend runs."""
    order = {name: i for i, name in enumerate(pg.graph.nodes)}
    return {p.name: sorted((o.name for s in p.stages for o in s.ops),
                           key=order.__getitem__)
            for p in pg.pipelines}


def _pass_lower_kernels(state: CompileState, opts: CompilerOptions) -> str:
    pg = _ensure_pipelined(state, opts)
    if opts.mode != "kitsune":
        # bsp/vertical never execute sf-node programs, so matching would be
        # wasted work and describe() would claim kernels that never run
        state.lowering = None
        return f"skipped: kernels only execute in kitsune mode ({opts.mode})"
    state.lowering = lower_pipelines(pg.graph, _pipelined_members(pg),
                                     hw=opts.resolved_hw(),
                                     policy=opts.lowering_policy)
    return state.lowering.summary()


def _skip_lower_kernels(state: CompileState, opts: CompilerOptions) -> str:
    _ensure_pipelined(state, opts)
    state.lowering = None
    return "kernel lowering disabled: every stage runs the jnp path"


def _pass_dedupe(state: CompileState, opts: CompilerOptions) -> str:
    """Bucket the artifact's lowerable programs by structural identity
    (core/pipeline.py `dedupe_programs`); the Engine caches param-less
    programs by these keys so structurally equal stages share ONE compiled
    executable (and one ExecutionPlan binding per stage slot)."""
    pg = _ensure_pipelined(state, opts)
    if opts.mode == "vertical":
        state.dedupe = None
        return "skipped: vertical mode runs one whole-graph program"
    if opts.mode == "kitsune":
        members_of = _pipelined_members(pg)
        matches_of = {
            name: (state.lowering.matches_for(name)
                   if state.lowering is not None else [])
            for name in members_of}
        state.dedupe = dedupe_programs(pg.graph, members_of, matches_of)
    else:  # bsp: one program per non-free op of the source graph
        state.dedupe = dedupe_programs(state.graph, {})
    return state.dedupe.summary()


def _skip_dedupe(state: CompileState, opts: CompilerOptions) -> str:
    _ensure_pipelined(state, opts)
    state.dedupe = None
    return "dedupe disabled: every program keyed by name"


def _pass_balance(state: CompileState, opts: CompilerOptions) -> str:
    pg = _ensure_pipelined(state, opts)
    hw = opts.resolved_hw()
    state.balance_results = {}
    for pipe in pg.pipelines:
        # DRAM / on-chip volumes for the bandwidth caps come from the model
        dram = sum(s.weight_bytes for s in pipe.stages)
        onchip = sum(q.total_bytes * (1 + len(q.consumers))
                     for q in pipe.queues)
        state.balance_results[pipe.name] = _balance_pipeline(
            pipe, hw, dram, onchip)
    return f"{len(state.balance_results)} pipelines balanced on {hw.name}"


def _skip_balance(state: CompileState, opts: CompilerOptions) -> str:
    _ensure_pipelined(state, opts)
    state.balance_results = {}
    return "unbalanced (1 unit per stage at execution)"


def _ensure_pipelined(state: CompileState, opts: CompilerOptions,
                      ) -> PipelinedGraph:
    """Materialize the PipelinedGraph from whatever the passes produced.

    Called lazily by the first consumer (balance pass or compile() itself),
    so `create_queues` and `epilogue_fuse` may run in either order."""
    if state.pipelined is not None:
        return state.pipelined
    g = _ensure_work(state, opts)
    sel = _ensure_selection(state, opts)
    pipelines: list[Pipeline] = []
    for sf in sel.sf_nodes:
        members = state.members_of[sf.name]
        if sf.name in state.stages_of:
            stages, op_to_stage = state.stages_of[sf.name]
        else:
            stages, op_to_stage = fuse_epilogues(g, sf.name, members)
        queues, edges = materialize_queues(
            sf.name, stages, state.op_queues.get(sf.name, []), op_to_stage,
            opts.tile_bytes)
        pipelines.append(Pipeline(sf.name, stages, queues, sf, edges))
    state.pipelined = PipelinedGraph(g, pipelines)
    return state.pipelined


_PASSES: dict[str, tuple[Callable, Callable]] = {
    "select": (_pass_select, _skip_select),
    "split_reduction": (_pass_split_reduction, _skip_split_reduction),
    "create_queues": (_pass_create_queues, _skip_create_queues),
    "epilogue_fuse": (_pass_epilogue_fuse, _skip_epilogue_fuse),
    "lower_kernels": (_pass_lower_kernels, _skip_lower_kernels),
    "dedupe": (_pass_dedupe, _skip_dedupe),
    "balance": (_pass_balance, _skip_balance),
}


class PassManager:
    """Runs the compiler stages as named, introspectable passes.

    `passes` selects and ORDERS the passes (default: the canonical
    Algorithm-1 order).  Disabled passes (options.disable / balance=False)
    still appear in the records, marked disabled, and run their identity
    fallback so later passes see consistent state."""

    def __init__(self, passes: tuple[str, ...] | list[str] | None = None):
        names = tuple(passes) if passes is not None else PASS_NAMES
        for n in names:
            if n not in _PASSES:
                raise ValueError(f"unknown pass {n!r} (known: {PASS_NAMES})")
        self.pass_names = names

    def run(self, state: CompileState, options: CompilerOptions,
            ) -> list[PassRecord]:
        records: list[PassRecord] = []
        disabled = options.disabled
        for name in self.pass_names:
            run_fn, skip_fn = _PASSES[name]
            fn = skip_fn if name in disabled else run_fn
            with span(f"pass/{name}") as sp:
                summary = fn(state, options)
            records.append(PassRecord(name, sp.seconds, name in disabled,
                                      summary))
            if options.dump_ir is not None:
                options.dump_ir(name, state)
        return records


class CompiledApp:
    """The artifact `repro.compile()` returns: pipelined IR + balance plan +
    a mode-specific executor whose XLA executables are cached process-wide.

    run() with same-shaped feeds never re-lowers: the first call per shape
    populates the cache; later calls (and later CompiledApps of an identical
    graph+options) reuse the same compiled objects."""

    def __init__(self, graph: Graph, options: CompilerOptions,
                 state: CompileState, pass_records: list[PassRecord],
                 donate_feeds: frozenset[str] = frozenset()):
        self.graph = graph
        self.options = options
        self.state = state
        self.pass_records = pass_records
        self.donate_feeds = frozenset(donate_feeds)
        self.selection = state.selection
        self.pipelined = state.pipelined
        self.lowering = state.lowering
        self.dedupe = state.dedupe
        self.balance_results = state.balance_results
        self.fingerprint = graph_fingerprint(graph)
        if options.mode == "kitsune":
            # execute the POST-pass graph: reductions split, stage structure
            # fixed; sf programs follow the pipelined member lists (see
            # _pipelined_members), with lower_kernels matches replacing
            # member chains by real Pallas kernel calls.
            exec_graph = state.pipelined.graph
            members = _pipelined_members(state.pipelined)
            sf_members = [(p.name, members[p.name])
                          for p in state.pipelined.pipelines]
            lowering = state.lowering
        else:
            exec_graph = graph
            sf_members = []
            lowering = None
        backend = make_backend(options.mode, exec_graph, sf_members,
                               lowering)
        struct_keys = (state.dedupe.struct_keys
                       if state.dedupe is not None else None)
        self._engine = Engine(backend,
                              (self.fingerprint, options.cache_key()),
                              donate_feeds=self.donate_feeds,
                              struct_keys=struct_keys)

    # -- execution --------------------------------------------------------
    def run(self, feeds: dict[str, jax.Array], params: dict | None = None,
            ) -> ExecutionReport:
        return self._engine.run(feeds, params or {})

    def init_params(self, key: jax.Array, scale: float = 0.02,
                    dtype=None) -> dict[str, Any]:
        kw = {} if dtype is None else {"dtype": dtype}
        return init_params(self.graph, key, scale, **kw)

    def executables(self) -> list[tuple]:
        """Cache keys of this app's compiled programs (debug/introspection).

        Covers both the engine-namespaced entries and, when the dedupe pass
        ran, the canonical `("sfprog", struct_key, ...)` entries this app's
        programs bind to (those are shared: another app with structurally
        equal programs lists the same keys)."""
        prefix = self._engine.engine_key
        skeys = set(self._engine.struct_keys.values())
        return [k for k in executable_cache().keys()
                if k[:len(prefix)] == prefix
                or (k and k[0] == "sfprog" and k[1] in skeys)]

    def dedupe_stats(self) -> dict:
        """Structural-dedupe telemetry (programs, classes, hit rate) for
        this artifact's engine; all-zero hit rate when the pass is off."""
        return self._engine.dedupe_stats()

    # -- analytics --------------------------------------------------------
    def estimate(self, hw: HwSpec | None = None, mode: str | None = None,
                 ) -> GraphCost:
        """Analytic end-to-end cost (paper Figs 10-14) of this artifact's
        pipelined IR under `mode` (default: the compiled mode)."""
        return evaluate(self.pipelined, hw or self.options.resolved_hw(),
                        mode or self.options.mode)

    def describe(self) -> str:
        """Human-readable pass pipeline + artifact summary."""
        lines = [f"CompiledApp({self.graph.name}, mode={self.options.mode}, "
                 f"fingerprint={self.fingerprint})"]
        for r in self.pass_records:
            flag = " [disabled]" if r.disabled else ""
            lines.append(f"  pass {r.name:<16} {r.seconds * 1e3:8.2f} ms"
                         f"{flag}  {r.summary}")
        for p in self.pipelined.pipelines:
            lines.append(f"  pipeline {p.name}: "
                         f"{len(p.stages)} stages, {len(p.queues)} queues")
            low = (self.lowering.pipelines.get(p.name)
                   if self.lowering is not None else None)
            lowered_of = {}
            if low is not None:
                lowered_of = {op: m for m in low.matches for op in m.ops}
            for s in p.stages:
                alloc = self.balance_results.get(p.name)
                units = (alloc.allocation.get(s.name) if alloc else None)
                ustr = f" units={units}" if units is not None else ""
                kstr = ""
                kernels = sorted({lowered_of[o.name].label() for o in s.ops
                                  if o.name in lowered_of})
                if kernels:
                    kstr = f" kernel={'|'.join(kernels)}"
                lines.append(f"    stage {s.name} [{s.resource}]"
                             f" ops={[o.name for o in s.ops]}{ustr}{kstr}")
            for q in p.queues:
                lines.append(f"    queue {q.name}: {q.producer} -> "
                             f"{q.consumers} ({q.payload_bytes // 1024}KB"
                             f" x{q.depth})")
            if low is not None:
                for m in low.matches:
                    tag = "" if m.executable else " (plan-only)"
                    if m.verdict is not None:
                        word = "accepted" if m.verdict.lowered else "declined"
                        tag += f" [{word}: {m.verdict.reason()}]"
                    lines.append(f"    lowered {m.label()}{tag}: "
                                 f"{'+'.join(m.ops)} -> {m.out}")
                for op, why in low.fallbacks.items():
                    lines.append(f"    fallback {op}: {why}")
        if self.donate_feeds:
            rep = self.donation_report()
            lines.append(f"  donation declared={','.join(rep['declared_feeds'])}"
                         f" plans={rep['n_plans']}"
                         f" saved={rep['bytes_saved'] / 1e6:.2f}MB")
            for i, p in enumerate(rep["plans"]):
                note = " (declined)" if p["declined"] else ""
                lines.append(
                    f"    plan {i}: donated={p['donated_bytes'] / 1e6:.2f}MB "
                    f"aliased={p['aliased_bytes'] / 1e6:.2f}MB{note}")
                for name, e in sorted(p["feeds"].items()):
                    ok = "aliased" if e["aliased"] else "NOT aliased"
                    lines.append(f"      feed {name}: "
                                 f"{e['nbytes'] / 1e6:.3f}MB {ok}")
        return "\n".join(lines)

    def lowering_verdicts(self) -> list[dict]:
        """Per-site kernel-lowering verdict rows (kernel, ops, decision,
        source, estimate/measurement microseconds) -- the bench harness
        serializes these into BENCH_smoke.json's `lowering_verdicts`."""
        if self.lowering is None:
            return []
        return self.lowering.verdict_table()

    def donation_report(self) -> dict:
        """Which feeds XLA actually aliased in place, and bytes saved, per
        live ExecutionPlan (see Engine.donation_report)."""
        return self._engine.donation_report()

    def __repr__(self):
        return (f"CompiledApp({self.graph.name!r}, mode={self.options.mode!r}, "
                f"{len(self.pipelined.pipelines)} pipelines)")


class TracedApp(CompiledApp):
    """A CompiledApp built by tracing a jax callable (core/trace.py).

    Behaves like the original function: `app(*args)` feeds the positional
    arrays (plus the captured consts) through the compiled executor and
    returns outputs in the function's own pytree structure.  Weights live in
    the traced consts, so `init_params()` is empty and `run()` needs no
    params dict."""

    def __init__(self, traced: TracedFunction, options: CompilerOptions,
                 state: CompileState, pass_records: list[PassRecord],
                 donate_feeds: frozenset[str] = frozenset()):
        self.traced = traced
        self._calls = 0
        super().__init__(traced.graph, options, state, pass_records,
                         donate_feeds)

    def __call__(self, *args):
        with span("run", call=self._calls):
            self._calls += 1
            with span("feeds"):
                feeds = self.traced.feeds(*args)
                key, buf = self._engine.feed(feeds, {})
            report = self._engine.launch(key, buf, feeds, {})
            with span("outputs"):
                return self.traced.unflatten_outputs(report.outputs)

    def run(self, feeds: dict[str, jax.Array], params: dict | None = None,
            ) -> ExecutionReport:
        full = dict(self.traced.consts)
        full.update(feeds)
        return super().run(full, params)

    def init_params(self, key: jax.Array, scale: float = 0.02,
                    dtype=None) -> dict:
        return {}  # weights are captured consts, fed automatically

    def __repr__(self):
        return (f"TracedApp({self.graph.name!r}, mode={self.options.mode!r}, "
                f"{len(self.graph.nodes)} nodes, "
                f"{len(self.traced.consts)} consts)")


def compile(graph: Graph | Callable, *args,
            options: CompilerOptions | None = None,
            example_inputs: tuple | None = None,
            pass_manager: PassManager | None = None,
            donate_argnums: tuple[int, ...] = (),
            donate_feeds: tuple[str, ...] = (),
            **option_overrides) -> CompiledApp:
    """Compile an operator graph OR any jax callable into a CompiledApp.

    Graphs: `repro.compile(g)` / `repro.compile(g, mode="vertical")` /
    `repro.compile(g, CompilerOptions(...))`.
    Callables: `repro.compile(fn, example_inputs)` (optionally with a
    CompilerOptions third positional / keyword) traces `fn` through
    `jax.make_jaxpr` -- tracing is pass 0 of the pipeline -- and returns a
    TracedApp that is itself callable like `fn`.  `example_inputs` is the
    tuple of positional example arguments (a single array may be passed
    bare).

    Donation: `donate_argnums` (callable path) marks positional arguments
    whose buffers the compiled app may reuse once they are dead -- the
    training step donates its (state,) argument so parameter and optimizer
    buffers update in place instead of doubling resident memory.  As with
    `jax.jit`, a donated argument's arrays are CONSUMED by the call; pass
    fresh arrays (e.g. the previous call's outputs) each time.
    `donate_feeds` is the graph-path equivalent, naming feed keys directly."""
    for a in args:
        if isinstance(a, CompilerOptions):
            if options is not None:
                raise TypeError("options given twice")
            options = a
        elif example_inputs is None:
            example_inputs = a
        else:
            raise TypeError(f"unexpected positional argument {a!r}")
    if options is None:
        options = CompilerOptions(**option_overrides)
    elif option_overrides:
        options = replace(options, **option_overrides)
    pm = pass_manager or PassManager()
    if not isinstance(graph, Graph) and callable(graph):
        if example_inputs is None:
            raise TypeError("repro.compile(fn, ...) needs example_inputs")
        if not isinstance(example_inputs, (tuple, list)):
            example_inputs = (example_inputs,)
        with span("pass/trace") as sp:
            traced = trace_fn(graph, *tuple(example_inputs),
                              roll_scans=options.roll_scans)
        rec = PassRecord("trace", sp.seconds, False,
                         f"{len(traced.graph.nodes)} nodes, "
                         f"{len(traced.consts)} consts")
        donate = set(donate_feeds)
        if donate_argnums:
            # map argument positions to the traced input names their
            # flattened leaves occupy (in_names is leaf-ordered)
            spans, start = [], 0
            for a in example_inputs:
                n = len(jax.tree_util.tree_flatten(a)[0])
                spans.append((start, start + n))
                start += n
            for i in donate_argnums:
                if not 0 <= i < len(spans):
                    raise ValueError(f"donate_argnums {i} out of range for "
                                     f"{len(spans)} example inputs")
                lo, hi = spans[i]
                donate.update(traced.in_names[lo:hi])
        state = CompileState(traced.graph)
        records = [rec] + pm.run(state, options)
        _ensure_pipelined(state, options)
        return TracedApp(traced, options, state, records,
                         frozenset(donate))
    if example_inputs is not None:
        raise TypeError("example_inputs is only valid when compiling a "
                        "callable")
    if donate_argnums:
        raise TypeError("donate_argnums is only valid when compiling a "
                        "callable (use donate_feeds for graphs)")
    state = CompileState(graph)
    records = pm.run(state, options)
    _ensure_pipelined(state, options)
    return CompiledApp(graph, options, state, records,
                       frozenset(donate_feeds))


# ---------------------------------------------------------------------------
# cached_jit: the executable cache for arbitrary jax callables
# ---------------------------------------------------------------------------

class CachedFunction:
    """A jax callable bound to the compiled-artifact cache.

    Replaces bare `jax.jit(fn)` in the serving/launch paths: the first call
    per argument-shape lowers+compiles (counted by `lowering_count()`);
    every later call -- including from a different instance constructed with
    the same `key` -- reuses the cached executable."""

    def __init__(self, fn: Callable, key: tuple, **jit_kwargs):
        self._fn = fn
        self._key = ("cached_jit",) + tuple(key)
        self._jit_kwargs = jit_kwargs

    def __call__(self, *args):
        cache = executable_cache()
        key = self._key + (_shape_key(args),)
        exe = cache.get_or_build(
            key,
            lambda: jax.jit(self._fn, **self._jit_kwargs).lower(*args).compile())
        return exe(*args)

    def lower(self, *args):
        return jax.jit(self._fn, **self._jit_kwargs).lower(*args)


def cached_jit(fn: Callable, *, key: tuple, **jit_kwargs) -> CachedFunction:
    return CachedFunction(fn, key, **jit_kwargs)
