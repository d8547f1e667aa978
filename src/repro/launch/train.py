"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b \
        --steps 100 --reduced --mesh-data 1 --mesh-model 1

On a real slice this runs under `jax.distributed.initialize()` with one
process per host; here it drives the same code path on however many devices
exist (use --reduced on CPU).  Fault tolerance: Supervisor + Checkpointer;
data: host-sharded synthetic pipeline; parallelism: FSDP(data) x TP(model)
via the logical-axis rules.

`--compile-mode kitsune` routes the FULL training step (forward, backward,
loss, optimizer) through the dataflow pipeline instead of one jit: the step
is traced into the operator graph with custom-vjp MLP/attention atomics,
`lower_kernels` binds the MLP blocks to the fused Pallas kernels in both
directions, and the ExecutionPlan donates the old state buffers so params
and optimizer moments update in place (safe with checkpointing: the
Checkpointer stages state to host before the next step runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import _axis_types_kw
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.data import DataConfig, SyntheticLM
from repro.distributed.sharding import NULL, Sharder
from repro.optim import Optimizer, adafactor, adamw, cosine_schedule
from repro.runtime import StragglerMonitor, Supervisor
from repro.train import (TrainConfig, compile_train_step, make_train_state,
                         make_train_step)


def make_optimizer(cfg: ArchConfig, steps: int) -> Optimizer:
    """AdamW on a cosine schedule; Adafactor above 100B parameters, where
    fp32 Adam moments outgrow the chips' HBM."""
    if cfg.param_count() > 100e9:
        return adafactor(1e-2)
    return adamw(cosine_schedule(3e-4, warmup=20, total=steps))


def init_train_state(cfg: ArchConfig, opt: Optimizer, sharder=NULL,
                     seed: int = 0) -> dict:
    """Seeded train state; under a mesh every leaf -- parameters AND
    optimizer moments -- is placed by the Sharder's rules."""
    state = make_train_state(cfg, opt, jax.random.PRNGKey(seed))
    if sharder is not NULL:
        state = jax.device_put(state, sharder.state_shardings(state))
    return state


def jit_train_step(cfg: ArchConfig, opt: Optimizer, tc: TrainConfig,
                   sharder=NULL):
    """The plain-jit training step.  The old state is donated, so
    parameters and moments update in place (feed each call the previous
    call's output state)."""
    return jax.jit(make_train_step(cfg, opt, tc, sharder=sharder),
                   donate_argnums=(0,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compile-mode", default=None,
                    choices=("bsp", "vertical", "kitsune"),
                    help="run the training step through the dataflow "
                         "pipeline (repro.compile of the full "
                         "fwd+bwd+optimizer step, state donated in place) "
                         "instead of a plain jit; single-device only")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n_dev = args.mesh_data * args.mesh_model
    if n_dev > 1:
        mesh = jax.make_mesh((args.mesh_data, args.mesh_model),
                             ("data", "model"), **_axis_types_kw(2))
        sharder = Sharder(mesh)
    else:
        sharder = NULL

    opt = make_optimizer(cfg, args.steps)
    tc = TrainConfig(remat=True, microbatches=args.microbatches,
                     xent_chunk=min(512, args.seq))
    if args.compile_mode is not None and n_dev > 1:
        raise SystemExit("--compile-mode drives the single-device dataflow "
                         "pipeline; use mesh 1x1")
    if args.compile_mode is not None:
        # built lazily on the first step (the compiled artifact traces on
        # the example state/batch; Supervisor may restore state from a
        # checkpoint first)
        compiled = {}

        def step_fn(state, batch):
            if "app" not in compiled:
                compiled["app"] = compile_train_step(
                    cfg, opt, tc, state=state, batch=batch,
                    compile_mode=args.compile_mode)
                print(compiled["app"].lowering.summary()
                      if compiled["app"].lowering is not None
                      else "(no kernel lowering in this mode)", flush=True)
            return compiled["app"](state, batch)
    else:
        step_fn = jit_train_step(cfg, opt, tc, sharder)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    ck = Checkpointer(args.ckpt, keep=3, async_save=True)
    sup = Supervisor(ck, checkpoint_every=args.ckpt_every,
                     heartbeat_path=args.ckpt + "/heartbeat")
    mon = StragglerMonitor()

    def init_state():
        return init_train_state(cfg, opt, sharder)

    def one_step(state, step):
        t0 = time.time()
        batch = {"tokens": jnp.asarray(data.batch(step)["tokens"])}
        state, m = step_fn(state, batch)
        act = mon.record(time.time() - t0)
        if act:
            print(f"[straggler] {act}", flush=True)
        if step % 10 == 0:
            print(f"step {step:5d} loss {float(m['loss']):.4f}", flush=True)
        return state

    state, report = sup.run(init_state=init_state, step_fn=one_step,
                            n_steps=args.steps)
    print(f"finished: {report}")


if __name__ == "__main__":
    main()
