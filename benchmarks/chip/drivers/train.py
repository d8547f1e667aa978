"""Training driver: a closed loop of optimizer steps on seeded batches.

Set-up builds the one step object the window drives (the kitsune-compiled
step, or the plain-jit step), with its state from the benchmark's seeded
weights, and drives it through the first three steps.  Their readings --
each step's loss, each leaf's gradient as AdamW's first moment holds it
after step 1, and each leaf's change after step 3 -- are compared with the
plain float32 reference once the window has closed and the program's state
is freed.  The window then runs whole steps until `--seconds` have passed.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic
import trace_reduce
import weights
from common import (CompileCounter, all_within, check, log,
                    memory_peak_bytes, profiled)

FIRST_STEPS = 3


def _stacked_norms(tree) -> dict:
    """Per-leaf, per-layer float32 norms of a program-layout tree, named as
    the reference names them (`<leaf>@<layer>` for layer leaves; slice g of
    `blocks/sub<j>` is layer g * P + j, P the number of subs)."""
    out = {}
    for k, v in tree.items():
        if k == "blocks":
            subs = len(v)
            for j in range(subs):
                for name, x in _flat_blocks(v[f"sub{j}"]).items():
                    per = jnp.sqrt(jnp.sum(
                        jnp.square(x.astype(jnp.float32)),
                        axis=tuple(range(1, x.ndim))))
                    for g in range(x.shape[0]):
                        out[f"{name}@{g * subs + j}"] = per[g]
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
    return out


def _flat_blocks(sub: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in sub.items():
        if isinstance(v, dict):
            out.update(_flat_blocks(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = v
    return out


def _first_moment(state) -> dict:
    """AdamW's first moment per parameter leaf, in the parameters' layout."""
    treedef = jax.tree_util.tree_structure(state["params"])
    pairs = treedef.flatten_up_to(state["opt"].inner)
    return jax.tree_util.tree_unflatten(treedef, [m for m, _ in pairs])


def leaf_gaps(prog: dict, ref: dict, keep: list) -> dict:
    """Per leaf |norm_prog - norm_ref| / max(norm_ref, median leaf's)."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def worst(gaps: dict) -> tuple[float, str]:
    """The largest gap and its leaf; a NaN reading is the worst."""
    at = max(gaps, key=lambda k: np.inf if np.isnan(gaps[k]) else gaps[k])
    return gaps[at], at


def shares(norms: dict) -> dict:
    """Each leaf's norm as a share of the whole tree's norm (NaN, the worst
    reading, where the tree is all zero)."""
    total = float(np.sqrt(sum(v * v for v in norms.values())))
    return {k: v / total if total > 0 else float("nan")
            for k, v in norms.items()}


def compare(readings: dict, ref: dict, limits: dict) -> tuple[dict, dict]:
    """The numbers that decide `correct`, each against its limit.

    The step-1 gradient is compared leaf by leaf as each leaf's share of
    the whole clipped gradient's norm, so that the clip scale, which the
    program rounds to bfloat16 and which scales every leaf alike, is not
    read as a gap (Adam's update does not depend on it; PERF.md §2)."""
    gmed = float(np.median(list(ref["grad_norms"].values())))
    keep = [k for k, v in ref["grad_norms"].items()
            if v >= limits["leaf_floor"] * gmed]
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(readings["losses"], ref["losses"]))
    g, g_at = worst(leaf_gaps(shares(readings["grad_norms"]),
                              shares(ref["grad_norms"]), keep))
    d, d_at = worst(leaf_gaps(readings["delta_norms"], ref["delta_norms"],
                              keep))
    checks = {"loss_gap": check(loss, limits["loss_gap"]),
              "grad_gap": check(g, limits["grad_gap"]),
              "change_gap": check(d, limits["change_gap"])}
    info = {"leaves_compared": len(keep),
            "leaves_left_out": sorted(set(ref["grad_norms"]) - set(keep)),
            "grad_gap_at": g_at, "change_gap_at": d_at,
            "losses": readings["losses"], "ref_losses": ref["losses"]}
    return checks, info


def log_readings(readings: dict, ref: dict, info: dict) -> None:
    """Every leaf's readings, and where the worst gaps lie."""
    for k in ref["grad_norms"]:
        log(f"[leaf] {k} grad {readings['grad_norms'][k]!r} ref "
            f"{ref['grad_norms'][k]!r} change {readings['delta_norms'][k]!r} "
            f"ref {ref['delta_norms'][k]!r}")
    log(f"[check] leaves compared {info['leaves_compared']}, left out "
        f"{info['leaves_left_out']}; worst grad leaf {info['grad_gap_at']}, "
        f"worst change leaf {info['change_gap_at']}; losses "
        f"{info['losses']}, reference "
        f"losses {info['ref_losses']}")


def _faulty(step, fault: str | None):
    """Plant one named fault under the timed step (`run.py --fault`)."""
    if fault is None:
        return step
    if fault == "state_unchanged":
        def broken(state, batch):
            _, m = step(jax.tree.map(jnp.copy, state), batch)
            return state, m
    elif fault == "half_tokens":
        # half of the batch left out, in a form a batch of one can have:
        # each row's second half replaced by its first
        def broken(state, batch):
            t = batch["tokens"]
            h = t.shape[1] // 2
            return step(state, {"tokens": jnp.concatenate([t[:, :h]] * 2,
                                                          axis=1)})
    elif fault == "answer_altered":
        def broken(state, batch):
            new, m = step(state, batch)
            return new, dict(m, loss=m["loss"] + 0.01 * jnp.abs(m["loss"]))
    else:
        raise ValueError(f"unknown fault {fault!r}")
    return broken


def run(ctx) -> dict:
    from repro.launch.train import jit_train_step, make_optimizer
    from repro.models import get_model
    from repro.train import TrainConfig, compile_train_step

    ref = ctx.reference()
    a = ctx.ref_arch()
    hp = ctx.workload["optimizer"]
    mode = ctx.workload["options"]["step"]
    cfg = ctx.arch()
    tr = ctx.traffic
    tokens_per_step = tr["batch"] * tr["seq"]
    limits = ctx.workload["limits"]

    def ref_batches():
        return traffic.train_batches(ctx.seed, tr, a["vocab"])[:FIRST_STEPS]

    if ctx.control:
        return control(ctx, ref, a, hp, ref_batches, limits)

    counter = CompileCounter()
    opt = make_optimizer(cfg, hp["total_steps"])
    tc = TrainConfig(remat=True, xent_chunk=hp["xent_chunk"])
    rec: dict = {"mode": mode, "tokens_per_step": tokens_per_step}
    with ctx.span("weights"):
        shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
        subs = len(shapes["blocks"])
        params = weights.program_tree(ctx.seed, a, ref, subs)
        weights.check_layout(params, shapes)
        state = {"params": params, "opt": jax.jit(opt.init)(params)}
        del params
        batches = traffic.train_batches(ctx.seed, tr, a["vocab"])
        jax.block_until_ready((state, batches))

    def feed(i):
        return {"tokens": batches[i % len(batches)]}

    with ctx.span("compile"):
        t = time.perf_counter()
        if mode == "kitsune":
            step = compile_train_step(cfg, opt, tc, state=state,
                                      batch=feed(0), compile_mode="kitsune")
            rec["kitsune_compile_s"] = time.perf_counter() - t
            rec["lowering"] = _lowering(step)
        else:
            step = jit_train_step(cfg, opt, tc)
    step = _faulty(step, ctx.fault)

    norms = jax.jit(_stacked_norms)
    change_norms = jax.jit(lambda p, p0: _stacked_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), p, p0)))
    readings = {"losses": []}
    with ctx.span("first_steps"):
        for i in range(FIRST_STEPS):
            state, m = step(state, feed(i))
            readings["losses"].append(float(m["loss"]))
            if i == 0:
                b1 = hp["b1"]
                readings["grad_norms"] = {
                    k: float(v) / (1 - b1)
                    for k, v in norms(_first_moment(state)).items()}
        # the initial weights made again as the program got them: a jit's
        # bf16 output.  Made inside the norms' jit, XLA on the TPU may keep
        # them in float32 (excess precision), off by their bf16 rounding.
        p0 = weights.program_tree(ctx.seed, a, ref, subs)
        readings["delta_norms"] = {k: float(v) for k, v in
                                   change_norms(state["params"], p0).items()}
    del norms, change_norms, p0
    log(f"[train] {mode} first losses {readings['losses']}")
    setup_s = ctx.elapsed()
    c0 = counter.n

    n = 0
    with profiled(ctx) as prof, ctx.span("window"):
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while True:
            with ctx.span("step"):
                state, m = step(state, feed(FIRST_STEPS + n))
                jax.block_until_ready(m["loss"])
            n += 1
            if time.perf_counter() >= deadline:
                break
        window_s = time.perf_counter() - t0
    compiles = counter.n - c0
    last_loss = float(m["loss"])
    peak = memory_peak_bytes()
    log(f"[window] {n} steps in {window_s:.6f}s; compiles in window "
        f"{compiles}; last loss {last_loss}; peak_bytes_in_use {peak}")
    rec.update(window_s=window_s, steps=n, compiles=compiles,
               flops_per_token=ref.train_flops_per_token(a, tr["seq"]),
               peaks=ctx.peaks, shapes={"m": tokens_per_step,
                                        "d": a["d_model"], "f": a["d_ff"]})
    if ctx.trace:
        rec["trace"] = trace_reduce.reduce_dir(prof["dir"])
        trace_reduce.log_ops(rec["trace"])
    del state, step, m, batches
    gc.collect()

    with ctx.span("reference"):
        want = ref.train(ctx.seed, a, ref_batches(), hp, FIRST_STEPS)
    checks, info = compare(readings, want, limits)
    checks["compiles_in_window"] = check(compiles, 0)
    finite = bool(np.isfinite(last_loss))
    checks["window_loss_finite"] = check(0 if finite else 1, 0)
    log_readings(readings, want, info)
    tokens = n * tokens_per_step
    return {"correct": all_within(checks), "attempted": n + FIRST_STEPS,
            "failed": 0 if finite else 1, "checks": checks,
            "memory_peak_bytes": peak,
            "e2e": {"train_tokens_s": tokens / window_s, "setup_s": setup_s},
            "rec": rec}


def _lowering(app) -> dict:
    """Sites lowered or declined, by kernel and verdict source."""
    sites: dict = {}
    for p in app.lowering.pipelines.values():
        for km in p.matches:
            key = (f"{km.kernel}{'/gated' if km.meta.get('gated') else ''}:"
                   f"{'lowered' if km.accepted and km.executable else 'declined'}"
                   f":{km.verdict.source if km.verdict else 'forced'}")
            sites[key] = sites.get(key, 0) + 1
    for k, v in sorted(sites.items()):
        log(f"[lowering] {k} {v}")
    return sites


def control(ctx, ref, a, hp, ref_batches, limits) -> dict:
    """The reference at float8 in the program's place, read against the
    float32 reference with the cell's limits."""
    batches = ref_batches()
    low = ref.train(ctx.seed, a, batches, hp, FIRST_STEPS, quant=ref.fp8)
    want = ref.train(ctx.seed, a, batches, hp, FIRST_STEPS)
    checks, info = compare(low, want, limits)
    log_readings(low, want, info)
    return {"correct": all_within(checks), "attempted": FIRST_STEPS,
            "failed": 0, "checks": checks,
            "memory_peak_bytes": memory_peak_bytes(), "e2e": {}, "rec": {}}
