"""Train step: scan+remat forward, xent loss, grad clip, optimizer update,
optional microbatch gradient accumulation and compressed DP all-reduce.

Under jit with the sharding rules from distributed/sharding.py this lowers to
the FSDP(data) x TP(model) [x DP(pod)] program the dry-run compiles; gradient
reduction over the batch axes is inserted by GSPMD from the shardings (the
paper's Fig 2(b) batch-dim reduction, handled by mesh reduce-scatter trees).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import NULL
from repro.kernels import KernelConfig
from repro.models import get_model
from repro.optim import Optimizer, adamw, clip_by_global_norm


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1          # gradient-accumulation steps
    max_grad_norm: float = 1.0
    remat: bool = True
    z_loss: float = 1e-4           # logit regularizer (stabilizes bf16 LMs)
    # sequence-chunk width of the chunked cross entropy (peak logits memory
    # is O(chunk * vocab)); small values keep TRACED training graphs tiny
    # when the dataflow pipeline unrolls the xent scan (compile_train_step)
    xent_chunk: int = 512


def loss_fn(logits: jax.Array, tokens: jax.Array, z_loss: float = 0.0):
    """Next-token cross entropy, written to stay VOCAB-SHARDED.

    take_along_axis over a model-sharded vocab dim makes GSPMD all-gather
    the full f32 logits (measured: +124 GB/chip collective traffic and an
    OOM on llama4 train_4k -- EXPERIMENTS.md SS Perf iteration 1).  The
    iota/select/reduce form keeps every term vocab-local with one scalar
    psum, and the f32 upcast happens inside the reductions.

    Handles a non-token prefix (vlm patch embeddings): the text stream
    occupies the LAST `len(tokens)` logit positions."""
    targets = tokens[:, 1:]
    n = targets.shape[1]
    preds = logits[:, -n - 1:-1]          # position t-1 predicts target t
    pf = preds.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(pf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(pf - m), axis=-1)) + m[..., 0]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, preds.shape, 2)
    ll = jnp.sum(jnp.where(vocab_iota == targets[..., None], pf, 0.0), axis=-1)
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss


def chunked_softmax_xent(x: jax.Array, table: jax.Array, tokens: jax.Array,
                         z_loss: float = 0.0, chunk: int = 512,
                         sharder=NULL) -> jax.Array:
    """Cross entropy WITHOUT materializing (B, S, V) logits.

    x: (B, S, D) final hidden states; table: (V, D).  The sequence is
    processed in chunks: each chunk's logits (B, chunk, V) exist only inside
    a remat'd scan body, so peak memory drops from O(S*V) to O(chunk*V).
    Measured on llama4 train_4k: -15 GiB/chip of f32 logits temps
    (EXPERIMENTS.md SS Perf iteration 1b)."""
    targets = tokens[:, 1:]
    b, n = targets.shape
    xs = x[:, -n - 1:-1]                    # (B, n, D)
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    nc = (n + pad) // chunk
    xc = xs.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_fn(carry, ct):
        xi, ti = ct                          # (B, chunk, D), (B, chunk)
        logits = sharder.constrain(xi @ table.T, "logits").astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        vio = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = jnp.sum(jnp.where(vio == ti[..., None], logits, 0.0), axis=-1)
        valid = (ti >= 0).astype(jnp.float32)
        tot, totz, cnt = carry
        tot = tot + jnp.sum((lse - ll) * valid)
        totz = totz + jnp.sum(jnp.square(lse) * valid)
        return (tot, totz, cnt + jnp.sum(valid)), None

    (tot, totz, cnt), _ = jax.lax.scan(
        chunk_fn, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (xc, tc))
    loss = tot / cnt
    if z_loss:
        loss = loss + z_loss * totz / cnt
    return loss


def make_train_state(cfg: ArchConfig, opt: Optimizer, key=None):
    model = get_model(cfg)
    params = model.init(key if key is not None else jax.random.PRNGKey(0))
    return {"params": params, "opt": opt.init(params)}


def make_train_step(cfg: ArchConfig, opt: Optimizer,
                    tc: TrainConfig = TrainConfig(), *,
                    kernels: KernelConfig = KernelConfig(),
                    sharder=NULL) -> Callable:
    """Returns step(state, batch) -> (state, metrics).  jit/pjit-ready."""
    model = get_model(cfg)

    def fwd_loss(params, batch):
        hidden = model.forward(params, batch, kernels=kernels,
                               sharder=sharder, remat=tc.remat,
                               return_hidden=True)
        table = params.get("unembed", params["embed"])
        return chunked_softmax_xent(hidden, table, batch["tokens"],
                                    tc.z_loss, chunk=tc.xent_chunk,
                                    sharder=sharder)

    def step(state, batch):
        params = state["params"]
        if tc.microbatches > 1:
            # split the local batch over accumulation steps (scan: keeps one
            # microbatch of activations live -> the memory/throughput dial)
            def micro(acc, mb):
                l, g = jax.value_and_grad(fwd_loss)(params, mb)
                return jax.tree.map(jnp.add, acc,
                                    {"loss": l, "grads": g}), None

            mbs = jax.tree.map(
                lambda x: x.reshape(tc.microbatches,
                                    x.shape[0] // tc.microbatches,
                                    *x.shape[1:]), batch)
            zero = {"loss": jnp.zeros(()),
                    "grads": jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)}
            acc, _ = jax.lax.scan(micro, zero, mbs)
            loss = acc["loss"] / tc.microbatches
            grads = jax.tree.map(lambda g: g / tc.microbatches, acc["grads"])
        else:
            loss, grads = jax.value_and_grad(fwd_loss)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, tc.max_grad_norm)
        new_params, new_opt = opt.update(grads, state["opt"], params)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def compile_train_step(cfg: ArchConfig, opt: Optimizer,
                       tc: TrainConfig = TrainConfig(), *,
                       state, batch, compile_mode: str = "kitsune",
                       donate_state: bool = True, **compile_kwargs):
    """The full training step -- forward, backward, loss, optimizer update --
    compiled through the dataflow pipeline.

    Traces `make_train_step(cfg, opt, tc)` on the example (state, batch)
    under `models.atoms.dataflow_training()`, so the MLP / SwiGLU blocks
    survive capture as custom-vjp atomics in BOTH directions and the
    `lower_kernels` pass binds them to the real Pallas kernels
    (`fused_mlp_fwd` forward, `fused_mlp_bwd` backward -- the Fig 2(c)
    multicast, executable, not plan-only).  Attention stays single-node in
    both directions and lowers onto the flash-attention forward and its
    dQ / dK-dV backward pair where the operands fit the kernels.

    Returns a TracedApp: `app(state, batch) -> (state, metrics)`, same
    contract as the raw step.  With `donate_state` (default) the state
    argument's buffers are DONATED -- parameters and optimizer moments
    update in place, so feed each call the previous call's output state, not
    a retained copy.

    With `tc.microbatches > 1` the accumulation loop unrolls into
    structurally identical per-microbatch subgraphs; the compiler's
    `dedupe` pass keys them by structural identity so they share ONE
    compiled executable per unique structure (pass `disable=("dedupe",)`
    to opt out, or `roll_scans=True` to keep the loop as a single rolled
    node -- O(1) trace in the microbatch count, at the cost of hiding the
    body from sf-node selection).

    The serving analogue is `ServeConfig(compile_mode=...)`; this is the
    training side of the same switch."""
    import repro
    from repro.models import atoms

    step_fn = make_train_step(cfg, opt, tc)
    donate = (0,) if donate_state else ()
    with atoms.dataflow_training():
        return repro.compile(step_fn, (state, batch), mode=compile_mode,
                             donate_argnums=donate, **compile_kwargs)
