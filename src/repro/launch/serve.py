"""Production serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --engine async --requests 16 --batch 4 --max-len 64

``--engine`` picks the stack: ``paged`` (block-paged KV + chunked prefill,
the production default), ``async`` (the same engine behind the background
tick loop / streaming handles), or ``legacy`` (the contiguous-cache
baseline).  ``--compile-mode kitsune`` routes the decode tick through the
dataflow pipeline; ``--num-blocks`` overrides the profiled pool capacity
(useful on CPU).

Fault drills (docs/SERVING.md "Failure model"): ``--fault-plan`` installs a
scripted fault schedule, e.g. ``tick.step@4,tick.logits@6:rid=3`` (fire the
step fault at tick 4, poison request 3's logits at tick 6; ``site@*`` fires
every probe), ``--deadline-s`` puts a per-request deadline on every
submission, ``--max-queue`` bounds admission, and ``--nan-guard`` enables
the decode-logits guard.  The run prints ``health()`` and the per-request
failure breakdown at the end.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.core.lower import kernel_config
from repro.launch.cache import enable_compile_cache
from repro.models import get_model
from repro.serve import (AsyncServingEngine, EngineError, PagedServingEngine,
                         ServeConfig, ServingEngine, parse_fault_plan)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--engine", choices=["paged", "async", "legacy"],
                    default="paged")
    ap.add_argument("--compile-mode", default=None,
                    choices=["bsp", "vertical", "kitsune"])
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size; default: on-device profiling pass")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--fault-plan", default=None,
                    help="scripted fault schedule, e.g. "
                         "'tick.step@4,tick.logits@6:rid=3,pool.alloc@*'")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (DeadlineExceeded past it)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue (QueueFull backpressure)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="fail slots whose decode logits go NaN/Inf")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[2 + rid % 7, 11, 23] for rid in range(args.requests)]
    plan = parse_fault_plan(args.fault_plan) if args.fault_plan else ()
    sc = ServeConfig(max_len=args.max_len, batch=args.batch,
                     compile_mode=args.compile_mode,
                     num_blocks=args.num_blocks,
                     prefill_chunk=args.prefill_chunk,
                     fault_plan=plan, fault_seed=args.fault_seed,
                     nan_guard=args.nan_guard, max_queue=args.max_queue,
                     default_deadline_s=args.deadline_s)

    kernels = kernel_config()
    t0 = time.time()
    if args.engine == "legacy":
        eng = ServingEngine(cfg, params, sc, kernels=kernels, eos_id=-1)
        for rid, p in enumerate(prompts):
            eng.submit(rid, p)
        done = eng.run_until_done()
        extra = ""
    elif args.engine == "paged":
        eng = PagedServingEngine(cfg, params, sc, kernels=kernels, eos_id=-1)
        for rid, p in enumerate(prompts):
            eng.submit(p, rid=rid)
        done = eng.run_until_done()
        extra = f" stats={eng.stats()}"
        failed = eng.failed
    else:
        with AsyncServingEngine(cfg, params, sc, kernels=kernels,
                                eos_id=-1) as eng:
            handles = [eng.submit(p) for p in prompts]
            done, failed = {}, {}
            for h in handles:
                try:
                    done[h.rid] = h.result(timeout=600)
                except EngineError as exc:
                    failed[h.rid] = exc
        extra = f" stats={eng.engine.stats()}"
        eng = eng.engine
    dt = time.time() - t0
    toks = sum(len(v) for v in done.values())
    print(f"[{args.engine}] served {len(done)}/{args.requests} requests, "
          f"{toks} tokens in {dt:.1f}s ({toks / dt:.0f} tok/s){extra}")
    if args.engine != "legacy":
        print(f"health: {eng.health()}")
        for rid, err in sorted(failed.items()):
            print(f"  failed rid={rid}: {err!r}")


if __name__ == "__main__":
    main()
