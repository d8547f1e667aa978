"""`lower_kernels` pass: map pipelined sf-node stages onto REAL Pallas kernels.

Until this pass existed, the Kitsune backend executed every sf-node by
replaying the member ops' jnp closures under one `jax.jit` -- vertical fusion
per sf-node, not dataflow: the hand-written dataflow kernels in
`repro/kernels/` were only reachable from the model layers and the kernel
benches.  This pass closes that gap.  It pattern-matches each pipeline's
member ops (post split-reduction, post epilogue-fusion) onto the kernels:

  * GEMM -> act -> GEMM chains            -> kernels.mlp (fused_mlp_fwd):
    the (M, H) hidden tile streams through VMEM, never touching HBM
  * gate/up dual-GEMM -> mul -> down GEMM -> kernels.mlp_swiglu
  * attention ops                         -> flash_attention (prefill,
    sq == skv) or flash_decode (sq == 1 split-K decode)
  * reduce_partial -> reduce_final pairs  -> queue_reduce: the fan-in
    partials fold through a VMEM accumulator, one grid step per queue pop
  * dX/dW multicast GEMMs in synthesized backward graphs -> fused_mlp_bwd
    (plan-only: those graphs are cost-model artifacts and carry no weights,
    so the match is recorded for analysis but never executed)
  * HINTED atomics in traced training graphs (core/trace.py `atomic_vjp`
    with `lower=` hints, installed by models/atoms.py during training
    capture) -> EXECUTABLE kernel calls: fused_mlp / fused_mlp_swiglu
    forward and fused_mlp_bwd (two-matrix and gated) backward;
    flash_attention forward and flash_attention_bwd (the dQ / dK-dV pair)
    for the attention atoms.  The atomic registry pins those nodes'
    semantics, so opacity of the eval closure is not a bar -- this is how
    the backward of a real `jax.grad` training step runs the Fig 2(c)
    multicast kernels instead of replaying autodiff closures.

Every match is EXACT: a chain is only lowered when its intermediate values
are single-consumer-internal and the member ops' semantics are fully known
(builder nodes, or traced nodes without opaque closures), so lowered
execution is numerically interchangeable with the jnp path.  Anything that
does not match falls back to the jnp closure with a recorded REASON --
`CompiledApp.describe()` prints which stages lowered and why others did not.

Matching is necessary but NOT sufficient: a matched kernel may still lose
wall-clock to XLA's fused closure (interpret-mode overhead on CPU, launch
overhead on tiny sites).  Under `policy="auto"` (the compiler default) every
executable match also carries a profitability VERDICT: a roofline estimate
(`cost_kernel_site` vs `cost_vertical` on the active HwSpec) decides
clear-cut sites, and anything inside the uncertainty band is settled by a
one-shot compile-time microbenchmark of both candidates on the real feed
shapes.  Declined matches stay in the plan (visible in describe()) but fall
back to the jnp closure for execution.  Verdicts are cached process-wide by
(kernel pattern, shapes, dtypes, hw) -- see executor.verdict_cache -- so
repeat compiles pay nothing.  `policy="always"` (the default for direct
`lower_pipelines` calls) preserves the historical force-lower behavior.

`kernel_config()` is the one platform probe: off-TPU the kernels run in
Pallas interpret mode (`interpret=True`), keeping the differential tests
executable on CPU CI.  On real TPUs they compile, and the lowering also
autotunes each kernel's block sizes over a small per-kernel candidate grid
(`tile_candidates` in the kernel modules; cached in kernels.autotune).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable

import jax
import numpy as np

from ..spans import span
from .costmodel import V5E, HwSpec, cost_kernel_site, cost_vertical
from .graph import Graph, Node

# Activation names whose kernel implementation matches the executor's
# `_EW_FNS` exactly (same jax.nn functions on both sides).
_LOWERABLE_ACTS = ("relu", "gelu", "silu", "identity")

# Estimate-tier uncertainty band (policy="auto" on real hardware): when the
# two roofline estimates are within this factor of each other, the analytic
# model cannot be trusted to pick a side and the site is microbenchmarked.
ESTIMATE_BAND = 1.5

# Measurement-tier decline bias: a measured kernel must beat the measured
# closure by this factor to be lowered.  The isolated closure OVERSTATES its
# in-program cost (inside the real program XLA fuses the member chain with
# its producers/consumers; the standalone jit cannot, while the opaque
# Pallas call gets no cross-boundary fusion either way), so near-parity
# measurements systematically favor the kernel -- and near-parity sites are
# exactly where lowering is not worth the risk of losing wall-clock.
MEASURE_MARGIN = 1.3

# Interleaved timing repetitions per candidate in the microbenchmark: the
# two candidates alternate (k, c, k, c, ...) and each keeps its min, so a
# host load spike lands on both sides instead of biasing whichever
# candidate happened to be in flight.
MEASURE_REPS = 5


def kernel_config():
    """THE platform probe: the Pallas kernels compiled (and autotuned) on a
    TPU, in interpret mode everywhere else (CPU tests).  Every entry point
    that runs kernels -- the lowering pass, the launchers, chip_smoke.py --
    takes its KernelConfig from here; the result is threaded through every
    matcher and kernel-call factory (the call closures must not re-probe
    the backend on every invocation)."""
    from repro.kernels import KernelConfig
    on_tpu = jax.default_backend() == "tpu"
    return KernelConfig(use_pallas=True, interpret=not on_tpu,
                        autotune=on_tpu)


# ---------------------------------------------------------------------------
# plan datatypes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Verdict:
    """Profitability verdict for one executable kernel match.

    `source` records which tier decided: "forced" (policy bypass),
    "cost" (roofline estimates were conclusive), "measured" (the one-shot
    microbenchmark settled it).  Times are microseconds; measured fields
    stay None when the estimate tier was conclusive.  `error` holds why a
    microbenchmark that should have decided could not run (the estimate
    decided instead)."""
    decision: str                        # "lowered" | "declined"
    source: str                          # "forced" | "cost" | "measured"
    est_kernel_us: float = 0.0
    est_closure_us: float = 0.0
    meas_kernel_us: float | None = None
    meas_closure_us: float | None = None
    error: str | None = None

    @property
    def lowered(self) -> bool:
        return self.decision == "lowered"

    def reason(self) -> str:
        if self.source == "forced":
            return "forced by policy"
        if self.source == "cost":
            why = (f"cost est kernel {self.est_kernel_us:.1f}us vs "
                   f"closure {self.est_closure_us:.1f}us")
            if self.error:
                why += f" (measurement failed: {self.error})"
            return why
        return (f"measured kernel {self.meas_kernel_us:.1f}us vs "
                f"closure {self.meas_closure_us:.1f}us")


@dataclass
class KernelMatch:
    """One group of sf-node member ops lowered onto one Pallas kernel call.

    `call(vals, params)` computes the value of `out` from the live value
    dict + param sub-dict; intermediate member values (strictly internal to
    the match) are never materialized.  `executable=False` marks plan-only
    matches (synthesized backward graphs, which cannot run at all).
    `verdict` is None until the profitability pass runs (policy != always);
    a declined verdict keeps the match in the plan but routes execution to
    the jnp fallback.  `_factory(cfg)` rebuilds the call under a different
    KernelConfig -- the block-size autotuner uses it to time candidates."""
    kernel: str
    ops: tuple[str, ...]
    out: str
    meta: dict = field(default_factory=dict)
    executable: bool = True
    verdict: Verdict | None = None
    _call: Callable | None = None
    _factory: Callable | None = None

    @property
    def accepted(self) -> bool:
        return self.verdict is None or self.verdict.lowered

    def call(self, vals: dict, params: dict):
        return self._call(vals, params)

    def label(self) -> str:
        m = ",".join(f"{k}={v}" for k, v in sorted(self.meta.items()))
        return f"{self.kernel}[{m}]" if m else self.kernel


@dataclass
class PipelineLowering:
    """Lowering outcome for one sf-node pipeline."""
    sf_name: str
    matches: list[KernelMatch]
    fallbacks: dict[str, str]  # member op -> reason it stays on the jnp path

    @property
    def lowered_ops(self) -> set[str]:
        return {o for m in self.matches if m.accepted for o in m.ops}


@dataclass
class LoweringPlan:
    """Per-pipeline kernel matches + fallback reasons (pass artifact)."""
    pipelines: dict[str, PipelineLowering]

    def matches_for(self, sf_name: str) -> list[KernelMatch]:
        pl = self.pipelines.get(sf_name)
        if pl is None:
            return []
        return [m for m in pl.matches if m.executable and m.accepted]

    def n_matches(self) -> int:
        return sum(len(p.matches) for p in self.pipelines.values())

    def lowered_ops(self) -> set[str]:
        return {o for p in self.pipelines.values() for o in p.lowered_ops}

    def kernels_used(self) -> list[str]:
        return sorted({m.kernel for p in self.pipelines.values()
                       for m in p.matches})

    def signature(self) -> tuple:
        """Hashable identity for executable-cache keys: two compiles with
        different lowering decisions must never share executables."""
        return tuple(
            (name, tuple((m.kernel, m.ops, m.executable, m.accepted)
                         for m in pl.matches))
            for name, pl in sorted(self.pipelines.items()))

    def verdict_table(self) -> list[dict]:
        """Per-site verdict rows (bench artifact / describe surface)."""
        rows = []
        for name, pl in sorted(self.pipelines.items()):
            for m in pl.matches:
                v = m.verdict
                rows.append({
                    "pipeline": name, "kernel": m.kernel,
                    "ops": list(m.ops), "out": m.out,
                    "executable": m.executable,
                    "decision": "lowered" if m.accepted else "declined",
                    "source": v.source if v else "forced",
                    "est_kernel_us": v.est_kernel_us if v else None,
                    "est_closure_us": v.est_closure_us if v else None,
                    "meas_kernel_us": v.meas_kernel_us if v else None,
                    "meas_closure_us": v.meas_closure_us if v else None,
                })
        return rows

    def summary(self) -> str:
        n_ops = len(self.lowered_ops())
        n_fb = sum(len(p.fallbacks) for p in self.pipelines.values())
        kern = ",".join(self.kernels_used()) or "none"
        base = (f"{self.n_matches()} kernel matches ({kern}) covering "
                f"{n_ops} ops; {n_fb} ops on the jnp fallback path")
        verdicts = [m.verdict for p in self.pipelines.values()
                    for m in p.matches if m.verdict is not None]
        if verdicts:
            n_dec = sum(1 for v in verdicts if not v.lowered)
            base += (f"; verdicts: {len(verdicts) - n_dec} accepted, "
                     f"{n_dec} declined")
        return base


# ---------------------------------------------------------------------------
# kernel-call closures
# ---------------------------------------------------------------------------

def _mlp_call(x_name: str, l1: str, l2: str, act: str, cfg) -> Callable:
    def call(vals, params):
        from repro.kernels import mlp
        return mlp(vals[x_name], params[l1]["w"], params[l2]["w"], act=act,
                   cfg=cfg)
    return call


def _swiglu_call(x_name: str, lg: str, lu: str, ld: str, act: str,
                 cfg) -> Callable:
    def call(vals, params):
        from repro.kernels import mlp_swiglu
        return mlp_swiglu(vals[x_name], params[lg]["w"], params[lu]["w"],
                          params[ld]["w"], act=act, cfg=cfg)
    return call


def _attention_call(node: Node, decode: bool, cfg) -> Callable:
    causal = bool(node.attrs.get("causal", True))
    q_name, k_name, v_name = node.inputs

    def call(vals, params):
        from repro.kernels import attention, decode_attention
        q, k, v = vals[q_name], vals[k_name], vals[v_name]
        if decode:
            return decode_attention(q, k, v, cfg=cfg)
        return attention(q, k, v, causal=causal, window=None, cfg=cfg)
    return call


def _atomic_mlp_fwd_call(inputs: list[str], meta: dict, cfg) -> Callable:
    x, w1, w2 = inputs
    act = meta.get("act", "identity")

    def call(vals, params):
        from repro.kernels import mlp
        return mlp(vals[x], vals[w1], vals[w2], act=act, cfg=cfg)
    return call


def _atomic_swiglu_fwd_call(inputs: list[str], meta: dict, cfg) -> Callable:
    x, wg, wu, wd = inputs
    act = meta.get("act", "identity")

    def call(vals, params):
        from repro.kernels import mlp_swiglu
        return mlp_swiglu(vals[x], vals[wg], vals[wu], vals[wd], act=act,
                          cfg=cfg)
    return call


def _atomic_mlp_bwd_call(inputs: list[str], meta: dict, cfg) -> Callable:
    x, w1, w2, dy = inputs
    act = meta.get("act", "identity")

    def call(vals, params):
        from repro.kernels import mlp_bwd
        return mlp_bwd(vals[x], vals[w1], vals[w2], vals[dy], act=act,
                       cfg=cfg)
    return call


def _atomic_swiglu_bwd_call(inputs: list[str], meta: dict, cfg) -> Callable:
    x, wg, wu, wd, dy = inputs
    act = meta.get("act", "identity")

    def call(vals, params):
        from repro.kernels import mlp_swiglu_bwd
        return mlp_swiglu_bwd(vals[x], vals[wg], vals[wu], vals[wd],
                              vals[dy], act=act, cfg=cfg)
    return call


def _atomic_attention_fwd_call(inputs: list[str], meta: dict,
                               cfg) -> Callable:
    q, k, v, *w = inputs
    causal = bool(meta["causal"])
    cfg = replace(cfg, block_q=meta["block"], block_k=meta["block"])

    def call(vals, params):
        from repro.kernels import attention
        return attention(vals[q], vals[k], vals[v], causal=causal,
                         window=vals[w[0]] if w else None, cfg=cfg)
    return call


def _atomic_attention_bwd_call(inputs: list[str], meta: dict,
                               cfg) -> Callable:
    q, k, v, *w, dy = inputs
    causal = bool(meta["causal"])
    cfg = replace(cfg, block_q=meta["block"], block_k=meta["block"])

    def call(vals, params):
        from repro.kernels import attention_bwd
        return attention_bwd(vals[q], vals[k], vals[v], vals[dy],
                             causal=causal,
                             window=vals[w[0]] if w else None, cfg=cfg)
    return call


def _paged_decode_call(inputs: list[str], meta: dict, cfg) -> Callable:
    q, kp, vp, tbl, vl = inputs
    block_size = int(meta["block_size"])

    def call(vals, params):
        from repro.kernels import paged_decode_attention
        return paged_decode_attention(vals[q], vals[kp], vals[vp], vals[tbl],
                                      valid_len=vals[vl],
                                      block_size=block_size, cfg=cfg)
    return call


def _queue_reduce_call(partial: Node, cfg) -> Callable:
    x_name = partial.inputs[0]

    def call(vals, params):
        from repro.core.executor import _eval_node
        from repro.kernels.queue_reduce import queue_reduce
        part = _eval_node(partial, [vals[x_name]], None)  # (fanin, *rest)
        fan, rest = part.shape[0], part.shape[1:]
        r = int(np.prod(rest[:-1])) if len(rest) > 1 else 1
        c = int(rest[-1]) if rest else 1
        br = min(cfg.block_r, r)
        if r % br:
            br = 1
        y = queue_reduce(part.reshape(fan, r, c), op="sum", block_rows=br,
                         interpret=cfg.interpret)
        return y.reshape(rest)
    return call


# ---------------------------------------------------------------------------
# matchers
# ---------------------------------------------------------------------------

def _mlp_gate(g: Graph, n: Node, meta: dict) -> str | dict:
    act = meta.get("act", "identity")
    if act not in _LOWERABLE_ACTS:
        return f"act {act!r} has no kernel implementation"
    if len(g.nodes[n.inputs[0]].out.shape) < 2:
        return "input rank < 2"
    return {}


def _attention_gate(g: Graph, n: Node, meta: dict) -> str | dict:
    """What the training kernels need of the operands: rank-4 q/k/v with
    whole GQA groups, self-attention (sq == skv, the causal diagonal at
    the origin), and a sequence the fixed tile rule divides."""
    from repro.kernels.flash_attention import train_block
    shapes = [tuple(g.nodes[i].out.shape) for i in n.inputs[:3]]
    if any(len(s) != 4 for s in shapes):
        return "q/k/v must be rank-4"
    if meta.get("windowed"):
        w = g.nodes[n.inputs[3]].out
        if w.shape != () or np.dtype(w.dtype) != np.int32:
            return "window must be an int32 scalar"
    (_, hq, sq, _), (_, hkv, skv, _) = shapes[0], shapes[1]
    if hq % hkv:
        return f"{hq} query heads do not group over {hkv} kv heads"
    if sq != skv:
        return f"needs sq == skv (got {sq} vs {skv})"
    block = train_block(sq)
    if block is None:
        return f"sequence {sq} not tileable"
    return {"block": block}


@dataclass(frozen=True)
class _Hinted:
    """One lower_hint family: the kernel label, operand count, call factory
    `(inputs, meta, cfg) -> call`, the operand gate `(g, node, meta) ->
    extra meta | reason`, and whether the match offers its factory to the
    tile search (False: the tiles come from a fixed rule).  An attention
    atom without a window operand has one operand fewer (hint `windowed`)."""
    kernel: str
    n_in: int
    factory: Callable
    gate: Callable
    extra: tuple = ()
    tuned: bool = True

    def operands(self, meta: dict) -> int:
        return self.n_in - (meta.get("windowed") is False)


_HINTED_KERNELS: dict[str, _Hinted] = {
    "mlp_fwd": _Hinted("fused_mlp", 3, _atomic_mlp_fwd_call, _mlp_gate),
    "swiglu_fwd": _Hinted("fused_mlp_swiglu", 4, _atomic_swiglu_fwd_call,
                          _mlp_gate),
    "mlp_bwd": _Hinted("fused_mlp_bwd", 4, _atomic_mlp_bwd_call, _mlp_gate),
    "swiglu_bwd": _Hinted("fused_mlp_bwd", 5, _atomic_swiglu_bwd_call,
                          _mlp_gate, extra=(("gated", True),)),
    "attention_fwd": _Hinted("flash_attention", 4, _atomic_attention_fwd_call,
                             _attention_gate, tuned=False),
    "attention_bwd": _Hinted("flash_attention_bwd", 5,
                             _atomic_attention_bwd_call, _attention_gate,
                             tuned=False),
    # block-table-native decode: operands are (q, kp, vp, tables, valid);
    # the pools are flat row pools, not activations, so nothing to gate
    "paged_decode": _Hinted("paged_decode", 5, _paged_decode_call,
                            lambda g, n, meta: {}),
}


def _try_hinted_atomic(g: Graph, n: Node, mset: set[str], taken: set[str],
                       note: Callable, cfg) -> KernelMatch | None:
    """Atomic nodes whose registry entry carries a kernel-lowering hint
    (core/trace.py `atomic(..., lower=...)` / `atomic_vjp`).  The hint pins
    the node's semantics, so opacity of the eval closure is NOT a bar: this
    is how traced training graphs get EXECUTABLE kernel matches in both
    directions -- fused_mlp / fused_mlp_swiglu and fused_mlp_bwd for the
    MLP atoms, instead of the plan-only dX/dW analysis of synthesized
    backwards, and flash_attention (forward) / flash_attention_bwd (the
    dQ / dK-dV pair) for the attention atoms, whose window is a runtime
    operand the kernels scalar-prefetch.  Each family gates on what the
    operands show; a site that fails its gate keeps its closure with the
    reason recorded.  The attention tiles come from a fixed rule
    (`flash_attention.train_block`), so those sites add no tile search."""
    hint = n.attrs.get("lower_hint")
    if not hint:
        return None
    family, *opts = hint
    meta = dict(tuple(kv) for kv in opts)
    spec = _HINTED_KERNELS.get(family)
    if spec is None:
        note(n.name, f"unknown lower hint {family!r}")
        return None
    n_in = spec.operands(meta)
    if len(n.inputs) < n_in:
        note(n.name, f"{spec.kernel}: expected {n_in} operands, "
                     f"got {len(n.inputs)}")
        return None
    gated = spec.gate(g, n, meta)
    if isinstance(gated, str):
        note(n.name, f"{spec.kernel}: {gated}")
        return None
    meta = {**meta, **dict(spec.extra), **gated}
    tuple_valued = "n_outs" in n.attrs and family.endswith("_fwd")

    def make(c):
        # the atom's own operands lead: partial evaluation (a layer scan's
        # loop-invariant hoisting) appends the values it hoisted out of the
        # impl after them, and the kernel does not read those
        call = spec.factory(list(n.inputs[:n_in]), meta, c)
        if tuple_valued:
            # atomic jit nodes are tuple-valued (projections index them):
            # the kernel call must honor the same convention as the eval
            # closure
            return lambda vals, params: (call(vals, params),)
        return call

    return KernelMatch(spec.kernel, (n.name,), n.name, meta, _call=make(cfg),
                       _factory=make if spec.tuned else None)

def _is_opaque(n: Node) -> bool:
    return "_eval" in n.attrs


def _sole_member_consumer(g: Graph, name: str, mset: set[str]) -> Node | None:
    cons = g.consumers(name)
    if len(cons) == 1 and cons[0].name in mset:
        return cons[0]
    return None


def _plain_linear(n: Node | None) -> bool:
    return (n is not None and n.kind == "linear" and not _is_opaque(n)
            and not n.attrs.get("bias"))


def _try_mlp(g: Graph, n: Node, mset: set[str], taken: set[str],
             note: Callable, cfg) -> KernelMatch | None:
    """L -> act -> L with single-consumer internals -> kernels.mlp."""
    if n.kind != "linear" or _is_opaque(n):
        return None
    if n.attrs.get("bias"):
        note(n.name, "fused_mlp: bias epilogue not supported by the kernel")
        return None
    if len(g.nodes[n.inputs[0]].out.shape) < 2:
        note(n.name, "fused_mlp: input rank < 2")
        return None
    act = _sole_member_consumer(g, n.name, mset)
    if (act is None or act.name in taken or act.kind != "elementwise"
            or _is_opaque(act) or len(act.inputs) != 1
            or act.attrs.get("fn") not in _LOWERABLE_ACTS):
        note(n.name, "lone GEMM: no single-consumer act->GEMM chain to fuse")
        return None
    l2 = _sole_member_consumer(g, act.name, mset)
    if not _plain_linear(l2) or l2.name in taken:
        note(n.name, "GEMM->act without a fusable second GEMM")
        return None
    fn = act.attrs["fn"]
    make = lambda c: _mlp_call(n.inputs[0], n.name, l2.name, fn, c)
    return KernelMatch(
        "fused_mlp", (n.name, act.name, l2.name), l2.name, {"act": fn},
        _call=make(cfg), _factory=make)


def _try_swiglu(g: Graph, n: Node, mset: set[str], taken: set[str],
                note: Callable, cfg) -> KernelMatch | None:
    """Gate/up dual GEMM -> elementwise mul -> down GEMM (Fig 2a SwiGLU
    shape; the builder's gate*up carries act=identity on the gate)."""
    if not _plain_linear(n) or len(g.nodes[n.inputs[0]].out.shape) < 2:
        return None
    ew = _sole_member_consumer(g, n.name, mset)
    if (ew is None or ew.name in taken or ew.kind != "elementwise"
            or _is_opaque(ew) or len(ew.inputs) != 2
            or ew.attrs.get("fn") != "mul"):
        return None
    other = ew.inputs[0] if ew.inputs[1] == n.name else ew.inputs[1]
    lu = g.nodes.get(other)
    if (not _plain_linear(lu) or lu.name in taken or lu.name not in mset
            or lu.inputs != n.inputs
            or _sole_member_consumer(g, lu.name, mset) is not ew):
        return None
    ld = _sole_member_consumer(g, ew.name, mset)
    if not _plain_linear(ld) or ld.name in taken:
        note(n.name, "dual-GEMM mul without a fusable down GEMM")
        return None
    lg, lu_ = (n.name, lu.name) if ew.inputs[0] == n.name else (lu.name, n.name)
    make = lambda c: _swiglu_call(n.inputs[0], lg, lu_, ld.name,
                                  "identity", c)
    return KernelMatch(
        "fused_mlp_swiglu", (n.name, lu.name, ew.name, ld.name), ld.name,
        {"act": "identity"}, _call=make(cfg), _factory=make)


def _try_attention(g: Graph, n: Node, mset: set[str], taken: set[str],
                   note: Callable, cfg) -> KernelMatch | None:
    if n.kind != "attention" or _is_opaque(n):
        return None
    if n.attrs.get("window"):
        note(n.name, "flash_attention: window mask not in executor semantics")
        return None
    shapes = [tuple(g.nodes[i].out.shape) for i in n.inputs]
    if len(shapes) != 3 or any(len(s) != 4 for s in shapes):
        note(n.name, "flash_attention: q/k/v must be rank-4")
        return None
    sq, skv = shapes[0][2], shapes[1][2]
    causal = bool(n.attrs.get("causal", True))
    if sq == 1 and causal:
        if skv % min(256, skv):
            note(n.name, "flash_decode: kv length not tileable")
            return None
        make = lambda c: _attention_call(n, True, c)
        return KernelMatch("flash_decode", (n.name,), n.name,
                           {"skv": skv}, _call=make(cfg), _factory=make)
    if causal and sq != skv:
        note(n.name, "flash_attention: causal offset needs sq == skv")
        return None
    if sq % min(128, sq) or skv % min(128, skv):
        note(n.name, "flash_attention: sequence not tileable")
        return None
    make = lambda c: _attention_call(n, False, c)
    return KernelMatch("flash_attention", (n.name,), n.name,
                       {"causal": causal, "sq": sq},
                       _call=make(cfg), _factory=make)


def _try_queue_reduce(g: Graph, n: Node, mset: set[str], taken: set[str],
                      note: Callable, cfg) -> KernelMatch | None:
    if n.kind != "reduce_partial" or _is_opaque(n):
        return None
    fin = _sole_member_consumer(g, n.name, mset)
    if (fin is None or fin.name in taken or fin.kind != "reduce_final"
            or _is_opaque(fin) or fin.inputs != [n.name]):
        note(n.name, "queue_reduce: fan-in stage without its final stage")
        return None
    make = lambda c: _queue_reduce_call(n, c)
    return KernelMatch("queue_reduce", (n.name, fin.name), fin.name,
                       {"fanin": int(n.attrs.get("fanin", 0))},
                       _call=make(cfg), _factory=make)


def _try_mlp_bwd(g: Graph, n: Node, mset: set[str], taken: set[str],
                 note: Callable, cfg) -> KernelMatch | None:
    """Fig 2(c) multicast in SYNTHESIZED backward graphs: the upstream grad
    feeds both the dX GEMM and a dW GEMM.  Those graphs are cost-model-only
    (single-input matmuls, no weights), so the match is plan-only."""
    if n.kind != "matmul" or _is_opaque(n) or len(n.inputs) != 1:
        return None
    dname = n.inputs[0]
    dw = next((c for c in g.consumers(dname)
               if c.name != n.name and c.name in mset and c.name not in taken
               and c.kind == "matmul" and len(c.inputs) == 2
               and dname in c.inputs and not _is_opaque(c)), None)
    if dw is None:
        return None
    return KernelMatch("fused_mlp_bwd", (n.name, dw.name), n.name,
                       {"multicast": dname}, executable=False)


_MATCHERS = (_try_hinted_atomic, _try_attention, _try_queue_reduce,
             _try_swiglu, _try_mlp, _try_mlp_bwd)


# ---------------------------------------------------------------------------
# microbenchmark + autotune plumbing
# ---------------------------------------------------------------------------

def _external_inputs(g: Graph, km: KernelMatch) -> list[str]:
    """Graph values a match reads from outside itself, in first-use order."""
    opset = set(km.ops)
    ext: list[str] = []
    for op in km.ops:
        for i in g.nodes[op].inputs:
            if i not in opset and i not in ext:
                ext.append(i)
    return ext


def _param_kinds(n: Node) -> bool:
    return n.kind in ("linear", "norm", "gather") and not _is_opaque(n)


def _synth_site(g: Graph, km: KernelMatch):
    """Deterministic feed-shaped inputs + weights for one match site.

    Random (non-zero) floats: closed-over or zero weights would let XLA
    constant-fold the closure candidate and bias the comparison.  Weights
    mirror executor.init_params' layout (linear w=(d_in,d_out), norm g,
    gather table)."""
    rng = np.random.default_rng(0)

    def synth(shape, dtype):
        dt = jax.numpy.dtype(dtype)
        if jax.numpy.issubdtype(dt, jax.numpy.integer):
            return jax.numpy.zeros(shape, dt)
        return jax.numpy.asarray(rng.standard_normal(shape), dtype=dt)

    vals = {name: synth(g.nodes[name].out.shape, g.nodes[name].out.dtype)
            for name in _external_inputs(g, km)}
    params: dict[str, Any] = {}
    for op in km.ops:
        n = g.nodes[op]
        if not _param_kinds(n):
            continue
        dt = n.out.dtype
        if n.kind == "linear":
            params[op] = {"w": synth((n.attrs["d_in"], n.attrs["d_out"]), dt)}
            if n.attrs.get("bias"):
                params[op]["b"] = jax.numpy.zeros((n.attrs["d_out"],),
                                                  jax.numpy.dtype(dt))
        elif n.kind == "norm":
            params[op] = {"g": jax.numpy.ones((n.out.shape[-1],),
                                              jax.numpy.dtype(dt))}
        elif n.kind == "gather":
            params[op] = {"table": synth(n.attrs["table"], dt)}
    return vals, params


def _site_runner(g: Graph, km: KernelMatch, vals: dict, params: dict):
    """(flat-arg kernel fn, flat-arg closure fn, args): every array -- feeds
    AND weights -- is a jit ARGUMENT, never a closed-over constant."""
    names = list(vals.keys())
    nv = len(names)
    pleaves, ptree = jax.tree_util.tree_flatten(params)
    args = tuple(vals[n] for n in names) + tuple(pleaves)

    def unpack(flat):
        v = dict(zip(names, flat[:nv]))
        p = jax.tree_util.tree_unflatten(ptree, list(flat[nv:]))
        return v, p

    def make_kernel_fn(call):
        def kernel_fn(*flat):
            v, p = unpack(flat)
            return call(v, p)
        return kernel_fn

    def closure_fn(*flat):
        from .executor import _eval_node
        v, p = unpack(flat)
        for op in km.ops:  # km.ops is topo-ordered by construction
            n = g.nodes[op]
            v[op] = _eval_node(n, [v[i] for i in n.inputs], p.get(op))
        return v[km.out]

    return make_kernel_fn, closure_fn, args


# Sites above these never microbenchmark: measuring means actually
# EXECUTING the site at compile time, and the paper-scale synthetic app
# graphs (estimate-only cost-model artifacts) would pay minutes of
# interpret-mode emulation per site (emulation cost scales with flops and
# grid steps, hence the flops cap on top of the footprint cap).  The tiny
# executable instances -- the graphs whose wall-clock the verdicts
# protect -- sit orders of magnitude below both caps.
MEASURE_CAP_BYTES = 64 << 20
MEASURE_CAP_FLOPS = 1e8


def _measurable(g: Graph, km: KernelMatch) -> bool:
    """Whether a site is small enough to execute at compile time."""
    def nbytes(spec) -> int:
        sz = np.dtype(spec.dtype).itemsize
        for d in spec.shape:
            sz *= int(d)
        return sz
    flops = sum(float(g.nodes[op].flops) for op in km.ops)
    if flops > MEASURE_CAP_FLOPS:
        return False
    total = sum(nbytes(g.nodes[i].out) for i in _external_inputs(g, km))
    total += sum(int(g.nodes[op].weight_bytes or 0) for op in km.ops)
    return total + nbytes(g.nodes[km.out].out) <= MEASURE_CAP_BYTES


def _measure_site(g: Graph, km: KernelMatch, cfg) -> tuple[float, float]:
    """One-shot microbenchmark of the kernel call vs the jnp-closure replay
    over the SAME member ops on feed-shaped random inputs.  Returns
    (kernel_s, closure_s); results are cached upstream in the verdict
    cache, so each unique site pays this once per process.

    The candidates are timed INTERLEAVED (min of MEASURE_REPS alternating
    runs each): back-to-back blocks would let one host load spike decide
    the verdict.  The whole measurement is one `verdict_measure` span."""
    import time as _time
    with span("verdict_measure", kernel=km.kernel):
        vals, params = _synth_site(g, km)
        make_kernel_fn, closure_fn, args = _site_runner(g, km, vals, params)
        fk = jax.jit(make_kernel_fn(km._call))
        fc = jax.jit(closure_fn)
        jax.block_until_ready(fk(*args))  # warmup: absorb compile
        jax.block_until_ready(fc(*args))
        t_kernel = t_closure = float("inf")
        for _ in range(MEASURE_REPS):
            t0 = _time.perf_counter()
            jax.block_until_ready(fk(*args))
            t_kernel = min(t_kernel, _time.perf_counter() - t0)
            t0 = _time.perf_counter()
            jax.block_until_ready(fc(*args))
            t_closure = min(t_closure, _time.perf_counter() - t0)
    return t_kernel, t_closure


def _shape_sig(g: Graph, km: KernelMatch) -> tuple:
    """Name-independent shape/dtype/structure identity of a match site."""
    opset = set(km.ops)
    relevant = ("fn", "act", "causal", "d_in", "d_out", "bias", "fanin",
                "transpose_b", "window", "n_outs", "lower_hint", "table")
    ext = tuple((tuple(g.nodes[i].out.shape), g.nodes[i].out.dtype)
                for i in _external_inputs(g, km))
    ops_sig = tuple(
        (g.nodes[op].kind, g.nodes[op].weight_bytes,
         tuple((k, tuple(v) if isinstance(v, list) else v)
               for k, v in sorted(g.nodes[op].attrs.items())
               if k in relevant))
        for op in km.ops)
    out = g.nodes[km.out].out
    return (km.kernel, tuple(sorted(km.meta.items())), ext, ops_sig,
            (tuple(out.shape), out.dtype))


def _tile_grid(g: Graph, km: KernelMatch) -> list[dict]:
    """Per-kernel block-size candidate grid for one match site (shapes read
    statically off the graph; the kernel modules own the grids)."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import fused_mlp as fm
    from repro.kernels import queue_reduce as qr
    if km.kernel in ("fused_mlp", "fused_mlp_swiglu", "fused_mlp_bwd"):
        first = g.nodes[km.ops[0]]
        x = g.nodes[first.inputs[0]].out.shape
        m = int(np.prod(x[:-1]))
        if first.kind == "linear":
            h = int(first.attrs["d_out"])
        else:  # hinted atomic: hidden dim off the first weight operand
            h = int(g.nodes[first.inputs[1]].out.shape[-1])
        return fm.tile_candidates(m, h)
    if km.kernel == "flash_attention":
        q = g.nodes[g.nodes[km.ops[0]].inputs[0]].out.shape
        k = g.nodes[g.nodes[km.ops[0]].inputs[1]].out.shape
        return fa.tile_candidates(q[2], k[2])
    if km.kernel == "flash_decode":
        k = g.nodes[g.nodes[km.ops[0]].inputs[1]].out.shape
        return fa.decode_tile_candidates(k[2])
    if km.kernel == "paged_decode":
        # split-K length comes off the block table, not the pool: every
        # chunk must cover whole pages, so candidates are page multiples
        tb = g.nodes[g.nodes[km.ops[0]].inputs[3]].out.shape
        bs = int(km.meta["block_size"])
        return fa.decode_tile_candidates(tb[1] * bs, page_size=bs)
    if km.kernel == "queue_reduce":
        rest = g.nodes[km.ops[0]].out.shape[1:]
        rows = int(np.prod(rest[:-1])) if len(rest) > 1 else 1
        return qr.tile_candidates(rows)
    return []


def _tune_match(g: Graph, km: KernelMatch, cfg):
    """Search the kernel's block-size grid on feed-shaped inputs; returns
    the winning KernelConfig (choices cached in kernels.autotune by
    name-independent site signature + platform)."""
    from repro.kernels.autotune import autotune
    cands = _tile_grid(g, km)
    if not cands or km._factory is None:
        return cfg
    key = ("tune", _shape_sig(g, km), jax.default_backend(), cfg.interpret)
    vals, params = _synth_site(g, km)
    make_kernel_fn, _, args = _site_runner(g, km, vals, params)

    def build(cand):
        return make_kernel_fn(km._factory(replace(cfg, **cand)))

    choice = autotune(key, cands, build, args, kernel=km.kernel)
    if "refused" in choice:
        km.meta["refused"] = choice["refused"]
    blocks = {k: v for k, v in choice.items() if k not in ("us", "refused")}
    if not blocks:
        return cfg
    km.meta.update(blocks)
    return replace(cfg, **blocks)


# ---------------------------------------------------------------------------
# profitability verdicts
# ---------------------------------------------------------------------------

def _verdict_key(g: Graph, km: KernelMatch, hw: HwSpec, cfg,
                 policy: str) -> tuple:
    return ("verdict", policy, _shape_sig(g, km), hw.name, cfg.interpret,
            jax.default_backend())


def _decide(g: Graph, km: KernelMatch, hw: HwSpec, cfg,
            policy: str) -> Verdict:
    """Two-tier profitability decision for one executable match.

    Tier 1 (roofline): `cost_kernel_site` vs `cost_vertical` over the same
    members on `hw`.  Conclusive on real hardware when the estimates differ
    by more than ESTIMATE_BAND.  Tier 2 (measurement): in interpret mode the
    analytic model cannot predict host wall-clock (a Pallas kernel emulated
    op-by-op loses to XLA by orders of magnitude regardless of rooflines),
    so `policy="auto"` always falls through to the microbenchmark there --
    unless the site exceeds the MEASURE_CAP_* limits, where measuring
    would mean executing a paper-scale site at compile time."""
    members = list(km.ops)
    est_k = cost_kernel_site(g, members, hw).time * 1e6
    est_c = cost_vertical(g, members, hw).time * 1e6
    if policy == "cost":
        dec = "lowered" if est_k <= est_c else "declined"
        return Verdict(dec, "cost", est_k, est_c)
    if not cfg.interpret:
        if est_k * ESTIMATE_BAND <= est_c:
            return Verdict("lowered", "cost", est_k, est_c)
        if est_c * ESTIMATE_BAND <= est_k:
            return Verdict("declined", "cost", est_k, est_c)
    if not _measurable(g, km):
        # too big to execute at compile time -- the estimate is the verdict
        dec = "lowered" if est_k <= est_c else "declined"
        return Verdict(dec, "cost", est_k, est_c)
    try:
        t_k, t_c = _measure_site(g, km, cfg)
    except Exception as exc:  # noqa: BLE001 - recorded in the verdict
        # measurement infeasible (e.g. unevaluable traced operand): the
        # estimate decides, and the verdict says why it had to
        dec = "lowered" if est_k <= est_c else "declined"
        why = (str(exc).strip().splitlines() or [""])[0][:160]
        return Verdict(dec, "cost", est_k, est_c,
                       error=f"{type(exc).__name__}: {why}")
    mk, mc = t_k * 1e6, t_c * 1e6
    dec = "lowered" if mk * MEASURE_MARGIN <= mc else "declined"
    return Verdict(dec, "measured", est_k, est_c, mk, mc)


def _apply_verdicts(g: Graph, plan: LoweringPlan, cfg, hw: HwSpec,
                    policy: str) -> None:
    from .executor import verdict_cache
    vc = verdict_cache()
    for pl in plan.pipelines.values():
        for km in pl.matches:
            if not km.executable:
                continue
            key = _verdict_key(g, km, hw, cfg, policy)
            v = vc.get(key)
            if v is None:
                v = _decide(g, km, hw, cfg, policy)
                vc.put(key, v)
            km.verdict = v
            if not v.lowered:
                for op in km.ops:
                    pl.fallbacks.setdefault(
                        op, f"declined {km.kernel}: {v.reason()}")


# ---------------------------------------------------------------------------
# pass body
# ---------------------------------------------------------------------------

def lower_pipeline(g: Graph, sf_name: str, members: list[str], *,
                   cfg=None) -> PipelineLowering:
    """Greedy scan of the member list (topo order) against the kernel
    matchers; unmatched non-free ops get a fallback reason."""
    if cfg is None:
        cfg = kernel_config()
    mset = set(members)
    taken: set[str] = set()
    matches: list[KernelMatch] = []
    notes: dict[str, str] = {}

    def note(op: str, why: str) -> None:
        notes.setdefault(op, why)

    for m in members:
        if m in taken:
            continue
        n = g.nodes[m]
        for matcher in _MATCHERS:
            km = matcher(g, n, mset, taken, note, cfg)
            if km is not None:
                if cfg.autotune and km.executable and km._factory is not None:
                    km._call = km._factory(_tune_match(g, km, cfg))
                matches.append(km)
                taken.update(km.ops)
                break
    fallbacks: dict[str, str] = {}
    for m in members:
        if m in taken:
            continue
        n = g.nodes[m]
        if n.is_free:
            continue
        if m in notes:
            fallbacks[m] = notes[m]
        elif _is_opaque(n):
            fallbacks[m] = ("traced node: closure semantics opaque to the "
                            "kernel matcher")
        else:
            fallbacks[m] = f"no kernel pattern for {n.kind}"
    return PipelineLowering(sf_name, matches, fallbacks)


def lower_pipelines(g: Graph, members_of: dict[str, list[str]], *,
                    cfg=None, hw: HwSpec | None = None,
                    policy: str = "always") -> LoweringPlan:
    """The `lower_kernels` pass body: one PipelineLowering per sf-node.

    `policy` selects the profitability gate on executable matches:
      * "always" -- every match lowers (historical behavior; default for
        direct calls so kernel-coverage tests stay force-lowered),
      * "cost"   -- roofline estimates alone decide,
      * "auto"   -- estimates decide clear-cut sites, the uncertainty band
        (and all of interpret mode) falls through to a one-shot
        microbenchmark; the compiler's default.
    Verdicts are cached process-wide (executor.verdict_cache) by
    name-independent site signature, so repeat compiles pay nothing."""
    if policy not in ("always", "cost", "auto"):
        raise ValueError(f"unknown lowering policy {policy!r}")
    if cfg is None:
        cfg = kernel_config()
    plan = LoweringPlan({name: lower_pipeline(g, name, members, cfg=cfg)
                         for name, members in members_of.items()})
    if policy != "always":
        _apply_verdicts(g, plan, cfg, hw if hw is not None else V5E, policy)
    return plan
