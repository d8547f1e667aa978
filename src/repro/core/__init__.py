"""Kitsune-on-TPU core: operator-graph IR, staged compiler, queues, executor.

The paper's SS5 flow is exposed as ONE front door (compiler.py):

    app = repro.compile(graph, CompilerOptions(mode=...))   # staged passes
    app.run(feeds, params)                                  # cached XLA exe

with the stages runnable as named passes through PassManager:

    select -> split_reduction -> create_queues -> epilogue_fuse ->
    lower_kernels -> dedupe -> balance

The historical free functions (select_subgraphs, design_pipeline, balance,
GraphExecutor) remain exported for direct pass-level use and tests; the
executor now runs behind per-mode backends (bsp | vertical | kitsune) with a
process-wide compiled-executable cache.
"""
from .graph import (Graph, Node, TensorSpec, MXU, VPU, graph_fingerprint,
                    node_struct_payload, program_struct_key,
                    structural_fingerprint, structural_hashes,
                    subgraph_interface)
from .patterns import select_subgraphs, Selection, SfNode, PATTERN_LIBRARY
from .pipeline import (design_pipeline, split_reductions, plan_queues,
                       fuse_epilogues, materialize_queues, OpQueue,
                       DedupeInfo, dedupe_programs,
                       PipelinedGraph, Pipeline, Stage, QueueSpec)
from .balance import solve_allocation, balance, BalanceResult
from .costmodel import (
    A100, V5E, HwSpec, v5e_mesh, device_hw, evaluate, cost_bsp, cost_vertical,
    cost_kitsune, cost_kernel_site, calibrate, roofline, RooflineTerms,
    utilization_quadrants,
    PEAK_FLOPS_PER_CHIP, HBM_BW_PER_CHIP, ICI_BW_PER_LINK,
)
from .queue import (
    queue_bandwidth, VMEM_QUEUE, ICI_QUEUE, L2_QUEUE_A100,
    spatial_pipeline, make_spatial_pipeline, ring_push,
)
from .executor import (GraphExecutor, ExecutorBackend, BSPBackend,
                       VerticalBackend, KitsuneBackend, make_backend,
                       ExecutionReport, ExecutionPlan, init_params,
                       compare_traffic, executable_cache,
                       clear_executable_cache, lowering_count,
                       verdict_cache, clear_verdict_cache)
from .lower import (KernelMatch, LoweringPlan, PipelineLowering, Verdict,
                    lower_pipeline, lower_pipelines)
from .trace import (trace, TracedFunction, atomic, attention_flops,
                    jaxpr_flops)
from .compiler import (CompilerOptions, CompiledApp, CompileState,
                       PassManager, PassRecord, TracedApp, cached_jit,
                       CachedFunction, compile)

__all__ = [
    "Graph", "Node", "TensorSpec", "MXU", "VPU", "graph_fingerprint",
    "node_struct_payload", "program_struct_key", "structural_fingerprint",
    "structural_hashes", "subgraph_interface",
    "select_subgraphs", "Selection", "SfNode", "PATTERN_LIBRARY",
    "design_pipeline", "split_reductions", "plan_queues", "fuse_epilogues",
    "materialize_queues", "OpQueue", "DedupeInfo", "dedupe_programs",
    "PipelinedGraph", "Pipeline", "Stage", "QueueSpec",
    "solve_allocation", "balance", "BalanceResult",
    "A100", "V5E", "HwSpec", "v5e_mesh", "device_hw", "evaluate", "cost_bsp",
    "cost_vertical", "cost_kitsune", "cost_kernel_site", "calibrate",
    "roofline", "RooflineTerms", "utilization_quadrants",
    "queue_bandwidth", "VMEM_QUEUE", "ICI_QUEUE", "L2_QUEUE_A100",
    "spatial_pipeline", "make_spatial_pipeline", "ring_push",
    "GraphExecutor", "ExecutorBackend", "BSPBackend", "VerticalBackend",
    "KitsuneBackend", "make_backend", "ExecutionReport", "ExecutionPlan",
    "init_params", "compare_traffic", "executable_cache",
    "clear_executable_cache", "lowering_count",
    "verdict_cache", "clear_verdict_cache",
    "KernelMatch", "LoweringPlan", "PipelineLowering", "Verdict",
    "lower_pipeline", "lower_pipelines",
    "CompilerOptions", "CompiledApp", "CompileState", "PassManager",
    "PassRecord", "cached_jit", "CachedFunction", "compile",
    "trace", "TracedFunction", "TracedApp", "atomic", "attention_flops",
    "jaxpr_flops",
]
