"""Compile rehearsals at real widths: the main-path Pallas kernels, compiled
through the `ops` wrappers (so the tiling rule itself is under test) for
one chip of a described TPU v5e topology.  Nothing runs; the TPU compiler
refuses here what it would refuse on the chip -- a tile that is not
(8, 128)-aligned, more VMEM than a kernel may use -- which interpret mode
never sees.  Widths are gemma3-1b's (d_model 1152, d_ff 6912, 4 query
heads and 1 KV head of 256) plus an 8 x 128 KV-head layout.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (KernelConfig, attention, decode_attention, mlp,
                           mlp_bwd, mlp_swiglu, mlp_swiglu_bwd,
                           paged_decode_attention, reduce)

D_MODEL, D_FF = 1152, 6912
KC = KernelConfig(use_pallas=True, interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def compile_on_chip(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF = jnp.bfloat16


@pytest.mark.parametrize("m", [4, 200, 512])   # decode batch, ragged, prefill
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_mlp_swiglu(one_chip, direction, m):
    w_in = ((D_MODEL, D_FF), BF)
    w_out = ((D_FF, D_MODEL), BF)
    x = ((m, D_MODEL), BF)
    if direction == "fwd":
        compile_on_chip(lambda x, g, u, d: mlp_swiglu(x, g, u, d, cfg=KC),
                        x, w_in, w_in, w_out, sharding=one_chip)
    else:
        compile_on_chip(
            lambda x, g, u, d, dy: mlp_swiglu_bwd(x, g, u, d, dy, cfg=KC),
            x, w_in, w_in, w_out, x, sharding=one_chip)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_mlp(one_chip, direction):
    w1, w2, x = ((D_MODEL, D_FF), BF), ((D_FF, D_MODEL), BF), ((512, D_MODEL), BF)
    if direction == "fwd":
        compile_on_chip(lambda x, a, b: mlp(x, a, b, cfg=KC), x, w1, w2,
                        sharding=one_chip)
    else:
        compile_on_chip(lambda x, a, b, dy: mlp_bwd(x, a, b, dy, cfg=KC),
                        x, w1, w2, x, sharding=one_chip)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention(one_chip, window):
    qkv = ((1, 4, 2048, 256), BF)
    compile_on_chip(lambda q, k, v: attention(q, k, v, window=window, cfg=KC),
                    qkv, qkv, qkv, sharding=one_chip)


def test_flash_decode(one_chip):
    compile_on_chip(
        lambda q, k, v, n: decode_attention(q, k, v, valid_len=n, cfg=KC),
        ((4, 4, 1, 256), BF), ((4, 1, 2048, 256), BF),
        ((4, 1, 2048, 256), BF), ((4,), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("site_pools", [False, True])   # (P,H,D) | (P,G,A,H,D)
@pytest.mark.parametrize("hkv,d", [(1, 256), (8, 128)])
def test_paged_flash_decode(one_chip, hkv, d, site_pools):
    b, bs, v_blocks, num_blocks = 4, 8, 64, 256
    rows = (num_blocks + 1) * bs
    pool = (rows, 26, 1, hkv, d) if site_pools else (rows, hkv, d)
    layer = (3, 0) if site_pools else None
    compile_on_chip(
        lambda q, kp, vp, t, n: paged_decode_attention(
            q, kp, vp, t, valid_len=n, block_size=bs, layer=layer, cfg=KC),
        ((b, 4 * hkv, 1, d), BF), (pool, BF), (pool, BF),
        ((b, v_blocks), jnp.int32), ((b,), jnp.int32), sharding=one_chip)


def test_queue_reduce(one_chip):
    compile_on_chip(lambda x: reduce(x, cfg=KC),
                    ((8, 512, D_MODEL), jnp.float32), sharding=one_chip)
