"""Dataflow Pallas kernels (pl.pallas_call + BlockSpec VMEM tiling).

Each kernel has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py;
models consume ops.py so one KernelConfig flag flips the implementation.
"""
from .ops import (KernelConfig, attention, attention_bwd, decode_attention,
                  mlp, mlp_bwd, mlp_swiglu, mlp_swiglu_bwd,
                  paged_decode_attention, reduce)
from .flash_attention import combine_partials
from .paged_attention import paged_flash_decode
from .autotune import autotune, time_fn, tune_cache

__all__ = ["KernelConfig", "attention", "attention_bwd", "decode_attention",
           "mlp", "mlp_bwd", "mlp_swiglu", "mlp_swiglu_bwd", "paged_decode_attention",
           "paged_flash_decode", "reduce", "combine_partials",
           "autotune", "time_fn", "tune_cache"]
