#!/usr/bin/env python3
"""On-chip smoke test: the serving engine and the kitsune training step on
gemma3-1b at its published widths (26 layers, d_model 1152, 4x256 query
heads, 1 KV head, d_ff 6912, vocab 262144, bf16), with seeded weights.

    python chip_smoke.py             # one chip: device, serve, train
    python chip_smoke.py --chips 4   # the sharded plain-jit training step
                                     # on a 2x2 (data, model) mesh vs one chip

One process holds the chip and starts no children.  There is no CPU
fallback: without a TPU it exits non-zero and prints no result.  Any phase
that fails makes the run fail; the last line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Every time and rate printed here is a smoke figure, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "gemma3-1b"

# Serving: the Pallas engine against the same engine on the XLA reference.
# Both run the same bf16 math; the fused-MLP kernel only sums the down
# projection in another order, so the logits agree to a few bf16 ulps.
SERVE_REQUESTS = 8
SERVE_BATCH = 4
SERVE_PROMPT = (32, 128)              # prompt lengths drawn in [lo, hi]
SERVE_NEW = 16                        # greedy new tokens per request
LOGIT_RTOL = 2e-2                     # max|dlogit| / max|ref logit|

# Training: the kitsune step-0 loss against the plain-jit step's.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 1, 512, 3
LOSS_RTOL = 1e-2                      # |dloss| / |ref loss|

# --chips 4: sharded losses against one device's, per step.
MESH_BATCH, MESH_SEQ, MESH_STEPS = 2, 256, 3
MESH_LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] {devs}")
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r});"
                 f" this script runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_prompts(cfg, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(SERVE_PROMPT[0], SERVE_PROMPT[1] + 1, SERVE_REQUESTS)
    return [rng.integers(2, cfg.vocab, n).tolist() for n in lens]


def serve_once(cfg, params, prompts, kernels) -> dict:
    """Serve `prompts` on a fresh PagedServingEngine (pool sized by the
    engine's own profiling pass) and record, per request, its tokens and
    the logits row its first token was sampled from."""
    from repro.serve import PagedServingEngine, ServeConfig
    sc = ServeConfig(max_len=SERVE_PROMPT[1] + SERVE_NEW, batch=SERVE_BATCH,
                     max_new_tokens=SERVE_NEW)
    t0 = time.perf_counter()
    eng = PagedServingEngine(cfg, params, sc, kernels=kernels, eos_id=-1)
    build_s = time.perf_counter() - t0
    for rid, p in enumerate(prompts):
        eng.submit(p, rid=rid)
    first_logits: dict[int, np.ndarray] = {}
    tick0 = None
    compile_s = steady_s = 0.0
    steady_tok = 0
    while True:
        n_prog, n_tok = len(eng._steps), eng.tokens_out
        t = time.perf_counter()
        pending = eng.tick()
        jax.block_until_ready(eng.kp)
        dt = time.perf_counter() - t
        if len(eng._steps) > n_prog:     # this tick compiled a program
            compile_s += dt
        else:
            steady_s += dt
            steady_tok += eng.tokens_out - n_tok
        if tick0 is None:
            tick0 = np.asarray(eng.last_logits, np.float32)
        for i, slot in enumerate(eng.slots):
            if slot is not None and len(slot["out"]) == 1 \
                    and slot["rid"] not in first_logits:
                first_logits[slot["rid"]] = np.asarray(
                    eng.last_logits[i], np.float32)
        if pending == 0:
            break
    if eng.failed:
        raise RuntimeError(f"requests failed: {eng.failed}")
    out = {"done": dict(eng.done), "tick0": tick0,
           "first_logits": first_logits, "build_s": build_s,
           "compile_s": compile_s, "steady_s": steady_s,
           "steady_tok": steady_tok, "tokens": eng.tokens_out,
           "blocks": eng.pool.num_blocks, "peak_bytes": peak_bytes()}
    del eng
    gc.collect()
    return out


def serve_phase(cfg, params, seed: int, kernels) -> dict:
    from repro.kernels import KernelConfig
    prompts = serve_prompts(cfg, seed)
    ref = serve_once(cfg, params, prompts, KernelConfig(use_pallas=False))
    got = serve_once(cfg, params, prompts, kernels)
    scale = float(np.max(np.abs(ref["tick0"])))
    worst = float(np.max(np.abs(got["tick0"] - ref["tick0"])))
    for rid in range(len(prompts)):
        r, g = ref["first_logits"][rid], got["first_logits"][rid]
        scale = max(scale, float(np.max(np.abs(r))))
        worst = max(worst, float(np.max(np.abs(g - r))))
        tr, tg = ref["done"][rid][0], got["done"][rid][0]
        # a differing first token is only admissible as a near-tie: the
        # reference must score the kernel's pick within the logit tolerance
        if tg != tr and r[tr] - r[tg] > LOGIT_RTOL * scale:
            raise AssertionError(
                f"request {rid}: first token {tg} (Pallas) != {tr} (XLA), "
                f"reference margin {r[tr] - r[tg]:.4g}")
        if len(got["done"][rid]) != SERVE_NEW:
            raise AssertionError(f"request {rid}: {len(got['done'][rid])} "
                                 f"tokens, expected {SERVE_NEW}")
    rel = worst / scale
    n_same = sum(ref["done"][r][0] == got["done"][r][0]
                 for r in range(len(prompts)))
    for name, res in (("xla", ref), ("pallas", got)):
        log(f"[serve:{name}] {len(res['done'])} requests, {res['tokens']} "
            f"tokens, pool {res['blocks']} blocks, build {res['build_s']:.2f}s,"
            f" compiling ticks {res['compile_s']:.2f}s; smoke rate (not a "
            f"benchmark): {res['steady_tok'] / max(res['steady_s'], 1e-9):.2f}"
            f" tok/s over {res['steady_s']:.2f}s of compiled ticks; "
            f"peak_bytes_in_use {res['peak_bytes']}")
    log(f"[serve] worst |dlogit| {worst:.6g} over max|logit| {scale:.6g} = "
        f"{rel:.6g} (tolerance {LOGIT_RTOL}); first tokens equal "
        f"{n_same}/{len(prompts)}")
    if not rel <= LOGIT_RTOL:
        raise AssertionError(f"serve logits differ by {rel:.4g} > "
                             f"{LOGIT_RTOL} of their scale")
    return {"rel_logit": rel, "first_equal": n_same}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_batch(cfg, batch: int, seq: int, step: int) -> dict:
    from repro.data import DataConfig, SyntheticLM
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch))
    return {"tokens": jnp.asarray(data.batch(step)["tokens"])}


def fit_train_shape(cfg, opt, batch: int, seq: int):
    """Compile the plain-jit step for (batch, seq); halve the sequence,
    then the batch, until its memory_analysis fits the device.  Widths are
    never cut.  Returns (batch, seq, compiled step)."""
    from repro.launch.train import jit_train_step
    from repro.train import TrainConfig, make_train_state
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    state_abs = jax.eval_shape(lambda: make_train_state(
        cfg, opt, jax.random.PRNGKey(0)))
    while True:
        tc = TrainConfig(remat=True, xent_chunk=min(512, seq))
        b_abs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
        compiled = jit_train_step(cfg, opt, tc).lower(state_abs,
                                                      b_abs).compile()
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        log(f"[train] plain-jit step at batch {batch} x seq {seq}: "
            f"{need} bytes needed, device limit {limit}")
        if limit is None or need <= limit:
            return batch, seq, tc, compiled
        if seq > 128:
            seq //= 2
        elif batch > 1:
            batch //= 2
        else:
            raise RuntimeError("the training step does not fit one chip "
                               "even at batch 1 x seq 128")
        log(f"[train] cut to batch {batch} x seq {seq} (widths unchanged)")


def train_phase(cfg, seed: int) -> dict:
    from repro.launch.train import init_train_state, make_optimizer
    from repro.train import compile_train_step
    opt = make_optimizer(cfg, TRAIN_STEPS)
    batch, seq, tc, ref_step = fit_train_shape(cfg, opt, TRAIN_BATCH,
                                               TRAIN_SEQ)
    batches = [train_batch(cfg, batch, seq, s) for s in range(TRAIN_STEPS)]

    state = init_train_state(cfg, opt, seed=seed)
    state, m = ref_step(state, batches[0])
    ref_loss = float(m["loss"])
    del state, m
    gc.collect()

    state = init_train_state(cfg, opt, seed=seed)
    t0 = time.perf_counter()
    app = compile_train_step(cfg, opt, tc, state=state, batch=batches[0],
                             compile_mode="kitsune")
    trace_s = time.perf_counter() - t0
    losses, step_s = [], []
    for s in range(TRAIN_STEPS):
        t = time.perf_counter()
        state, m = app(state, batches[s])
        losses.append(float(m["loss"]))
        step_s.append(time.perf_counter() - t)
    log(f"[train] lowering: {app.lowering.summary()}")
    matches = [km for p in app.lowering.pipelines.values() for km in p.matches]
    sites: dict = {}
    for km in matches:
        key = (km.kernel, "lowered" if km.accepted else "declined",
               km.verdict.source if km.verdict else "forced")
        sites[key] = sites.get(key, 0) + 1
    for (kernel, decision, source), n in sorted(sites.items()):
        log(f"[train]   {kernel}: {n} sites {decision} ({source})")
    shown = set()
    for km in matches:       # one site per MLP kernel: its tiles, refusals
        if km.kernel.startswith("fused_mlp") and km.kernel not in shown:
            shown.add(km.kernel)
            log(f"[train]   first {km.kernel} site: meta {km.meta}")
    lowered = {(km.kernel, bool(km.meta.get("gated")))
               for km in matches if km.executable and km.accepted}
    for want in (("fused_mlp_swiglu", False), ("fused_mlp_bwd", True)):
        if want not in lowered:
            raise AssertionError(f"SwiGLU kernel {want[0]} (gated={want[1]})"
                                 f" was not lowered: {sorted(lowered)}")
    log(f"[train] kitsune trace+compile {trace_s:.2f}s; step seconds (first "
        f"includes program compiles; smoke, not a benchmark) "
        f"{[round(t, 3) for t in step_s]}; peak_bytes_in_use {peak_bytes()}")
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    log(f"[train] batch {batch} x seq {seq}: kitsune losses {losses}; "
        f"plain-jit step-0 loss {ref_loss}; rel diff {rel:.6g} "
        f"(tolerance {LOSS_RTOL})")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite losses {losses}")
    if not rel <= LOSS_RTOL:
        raise AssertionError(f"step-0 loss differs by {rel:.4g}")
    del state, app
    gc.collect()
    return {"losses": losses, "ref_loss": ref_loss}


# ---------------------------------------------------------------------------
# --chips 4: sharded plain-jit training vs one device
# ---------------------------------------------------------------------------

def bytes_per_device(tree) -> dict:
    """Bytes of `tree`'s shards held by each device."""
    per: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return per


def mesh_phase(cfg, seed: int, devices) -> dict:
    from jax.sharding import Mesh
    from repro.distributed.sharding import Sharder
    from repro.launch.train import (init_train_state, jit_train_step,
                                    make_optimizer)
    from repro.train import TrainConfig
    opt = make_optimizer(cfg, MESH_STEPS)
    tc = TrainConfig(remat=True, xent_chunk=min(512, MESH_SEQ))
    batches = [train_batch(cfg, MESH_BATCH, MESH_SEQ, s)
               for s in range(MESH_STEPS)]

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    sharder = Sharder(mesh)
    state = init_train_state(cfg, opt, sharder, seed=seed)
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    for part in ("params", "opt"):
        per = bytes_per_device(state[part])
        full = sum(x.nbytes for x in jax.tree_util.tree_leaves(state[part]))
        log(f"[mesh] {part}: {full} bytes in all; per device "
            f"{[per.get(d, 0) for d in devices[:4]]}")
        if max(per.values()) > 0.4 * full:
            raise AssertionError(f"{part} are not spread over the mesh")
    step = jit_train_step(cfg, opt, tc, sharder)
    sharded = []
    for s in range(MESH_STEPS):
        state, m = step(state, batches[s])
        sharded.append(float(m["loss"]))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]]
    log(f"[mesh] 2x2 sharded losses {sharded}; bytes_in_use per device "
        f"{in_use} (train state {total} bytes in all)")
    del state
    gc.collect()

    state = init_train_state(cfg, opt, seed=seed)     # on devices[0]
    step = jit_train_step(cfg, opt, tc)
    single = []
    for s in range(MESH_STEPS):
        state, m = step(state, batches[s])
        single.append(float(m["loss"]))
    del state
    gc.collect()
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    log(f"[mesh] one-device losses {single}; worst rel diff {rel:.6g} "
        f"(tolerance {MESH_LOSS_RTOL})")
    if not all(np.isfinite(sharded)) or not rel <= MESH_LOSS_RTOL:
        raise AssertionError("sharded losses disagree with one device's")
    return {"sharded": sharded, "single": single}


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    device = device_check(args.chips)
    from repro.configs import get_config
    from repro.core.lower import kernel_config
    from repro.launch.cache import enable_compile_cache
    from repro.models import get_model
    log(f"[setup] compilation cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)

    if args.chips == 4:
        mesh_phase(cfg, args.seed, jax.devices())
    else:
        kernels = kernel_config()
        log(f"[setup] {cfg.name}: {cfg.n_layers} layers, d_model "
            f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
            f"kernels {kernels}")
        if kernels.interpret or not kernels.use_pallas:
            raise AssertionError(f"the probe chose {kernels} on a TPU")
        params = get_model(cfg).init(jax.random.PRNGKey(args.seed))
        serve_phase(cfg, params, args.seed, kernels)
        del params
        gc.collect()
        train_phase(cfg, args.seed)
    log(f"[done] {time.perf_counter() - t0:.1f}s in all")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
