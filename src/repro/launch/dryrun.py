import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

import argparse
import json
import re
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, applicable_shapes, get_config
from repro.configs.base import SHAPES
from repro.core.executor import executable_cache
from repro.distributed.sharding import Sharder
from repro.launch.inputs import input_specs, params_specs
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.optim import adafactor, adamw
from repro.serve.engine import serve_step
from repro.train import TrainConfig, make_train_step
from repro.core.costmodel import roofline

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes; report memory/cost/collective analysis (EXPERIMENTS.md
SS Dry-run) and the three roofline terms (SS Roofline).

No arrays are ever allocated: params/optimizer state/caches are
ShapeDtypeStructs via jax.eval_shape."""

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "f8": 1,
                "s8": 1, "u8": 1, "pred": 1}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^(]*\(", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Per-device wire bytes by collective type (ring model, documented in
    EXPERIMENTS.md): AR 2S(n-1)/n; AG/A2A S(n-1)/n; RS S_out(n-1);
    permute S."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0, "count": 0}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        size = _shape_bytes(m.group(1))
        op = m.group(2)
        g = _GROUPS_RE.search(line)
        n = int(g.group(2)) if g else 2
        n = max(n, 2)
        if op == "all-reduce":
            wire = 2 * size * (n - 1) / n
        elif op == "reduce-scatter":
            wire = size * (n - 1)
        elif op == "collective-permute":
            wire = size
        else:  # all-gather / all-to-all
            wire = size * (n - 1) / n
        out[op] += wire
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items() if k != "count")
    return out


# ---------------------------------------------------------------------------
# shardings for optimizer state and caches
# ---------------------------------------------------------------------------

def _full_spec(spec: P, ndim: int) -> tuple:
    t = tuple(spec)
    return t + (None,) * (ndim - len(t))


def opt_state_shardings(opt_name, params_sds, params_sh, mesh, opt_state_sds):
    rep = NamedSharding(mesh, P())
    if opt_name == "adamw":
        inner = jax.tree.map(lambda s: (s, s), params_sh)
    else:  # adafactor: (row, col) for ndim>=2, vector otherwise
        def fact(sds, sh):
            if len(sds.shape) >= 2:
                spec = _full_spec(sh.spec, len(sds.shape))
                return (NamedSharding(mesh, P(*spec[:-1])),
                        NamedSharding(mesh, P(*(spec[:-2] + spec[-1:]))))
            return sh
        inner = jax.tree.map(fact, params_sds, params_sh)
    from repro.optim.optimizers import OptState
    return OptState(step=rep, inner=inner)


def cache_shardings(sharder: Sharder, cache_sds: dict) -> dict:
    """KV cache: batch -> (pod,data); kv-heads -> model when divisible, else
    sequence-shard (distributed flash-decode); SSM states: batch + inner."""
    mesh = sharder.mesh
    b_axes = sharder.batch_axes
    out = {}
    for name, sds in cache_sds.items():
        shp = sds.shape
        if name in ("k", "v", "xk", "xv"):
            # (..., B, H, S, D) with 0-2 leading stack dims
            lead = len(shp) - 4
            B, H, S, D = shp[lead:]
            dims = [(shp[i], None) for i in range(lead)]
            if H % mesh.shape["model"] == 0:
                dims += [(B, b_axes), (H, "model"), (S, None), (D, None)]
            else:
                dims += [(B, b_axes), (H, None), (S, "model"), (D, None)]
            out[name] = sharder.named(dims)
        elif name == "ssm":   # (G, B, I, state)
            out[name] = sharder.named([(shp[0], None), (shp[1], b_axes),
                                       (shp[2], "model"), (shp[3], None)])
        else:                 # mlstm/slstm states: shard batch dim (idx 2)
            dims = [(shp[i], b_axes if i == 2 else None)
                    for i in range(len(shp))]
            out[name] = sharder.named(dims)
    return out


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def _lower_cell(cfg, shape_name: str, mesh, *, opt_kind: str):
    """Lower + compile one (config x shape) on `mesh`; returns compiled.

    Trace+lower+compile all go through the compiler's process-wide
    executable cache, so a cell revisited in one invocation (e.g. the same
    calibration depth across mesh variants) skips XLA entirely.  The key
    hashes the FULL config contents (not just its name: calibration cells
    reuse the name with replaced fields)."""
    key = ("dryrun", repr(cfg), shape_name, tuple(mesh.shape.items()),
           opt_kind)
    return executable_cache().get_or_build(
        key, lambda: _build_cell(cfg, shape_name, mesh, opt_kind=opt_kind))


def _build_cell(cfg, shape_name: str, mesh, *, opt_kind: str):
    shape = SHAPES[shape_name]
    sharder = Sharder(mesh)
    model = get_model(cfg)
    p_sds = params_specs(cfg, model)
    p_sh = sharder.params_shardings(p_sds)

    if shape.kind in ("train", "prefill"):
        batch_sds = input_specs(cfg, shape_name)
        batch_sh = {k: sharder.named(
            [(v.shape[0], sharder.batch_axes)]
            + [(d, None) for d in v.shape[1:]]) for k, v in batch_sds.items()}
        if shape.kind == "train":
            opt = adafactor(1e-2) if opt_kind == "adafactor" else adamw(1e-3)
            state_sds = jax.eval_shape(
                lambda: (lambda p: {"params": p, "opt": opt.init(p)})(
                    model.init(jax.random.PRNGKey(0))))
            state_sh = {"params": p_sh,
                        "opt": opt_state_shardings(
                            opt_kind, state_sds["params"], p_sh, mesh,
                            state_sds["opt"])}
            # giant MoE archs: 4-way gradient accumulation (the standard
            # memory/throughput dial; activations+dispatch shrink 4x)
            micro = 4 if opt_kind == "adafactor" else 1
            step = make_train_step(cfg, opt,
                                   TrainConfig(remat=True, microbatches=micro),
                                   sharder=sharder)
            rep = NamedSharding(mesh, P())
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh,
                                             {"loss": rep, "grad_norm": rep}),
                              donate_argnums=(0,)).lower(
                state_sds, batch_sds)
        else:  # prefill: hidden states for KV + LAST-token logits only
            def fwd(params, batch):
                x = model.forward(params, batch, sharder=sharder,
                                  return_hidden=True)
                table = params.get("unembed", params["embed"])
                return x[:, -1] @ table.T        # serving emits one token
            lowered = jax.jit(fwd, in_shardings=(p_sh, batch_sh)).lower(
                p_sds, batch_sds)
    else:  # decode
        state_sds = input_specs(cfg, shape_name)
        state_sh = {"tokens": sharder.named(
                        [(state_sds["tokens"].shape[0], sharder.batch_axes)]),
                    "pos": NamedSharding(mesh, P()),
                    "cache": cache_shardings(sharder, state_sds["cache"])}

        def sstep(params, state):
            return serve_step(params, state, cfg, sharder=sharder)

        vocab = cfg.vocab
        bsz = state_sds["tokens"].shape[0]
        out_sh = dict(state_sh)
        out_sh["logits"] = sharder.named([(bsz, sharder.batch_axes),
                                          (vocab, "model")])
        lowered = jax.jit(sstep, in_shardings=(p_sh, state_sh),
                          out_shardings=out_sh,
                          donate_argnums=(1,)).lower(
            p_sds, state_sds)
    return lowered.compile()


def _cost_triple(compiled) -> tuple[float, float, float]:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll["total"])


def _cal_period(cfg) -> int:
    """Calibration depth: one full structural+schedule period."""
    import math as _m
    from repro.models.lm import _sub_kinds
    period = len(_sub_kinds(cfg))
    if cfg.window_pattern:
        period = _m.lcm(period, len(cfg.window_pattern))
    return period


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    """Full compile for memory/sharding proof + calibrated cost extrapolation.

    XLA's cost_analysis counts a while-loop (scan) body ONCE, so per-layer
    cost comes from two small UNROLLED lowerings (depth P and 2P); the full
    model's cost is cal(P) + (L/P - 1) * [cal(2P) - cal(P)].  All numbers
    still come from compiled artifacts.
    """
    import dataclasses as _dc
    from repro.models import lm as lm_mod
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    opt_kind = "adafactor" if cfg.param_count() > 100e9 else "adamw"

    t0 = time.time()
    compiled = _lower_cell(cfg, shape_name, mesh, opt_kind=opt_kind)
    t_compile = time.time() - t0

    # calibration pass (single-pod numbers are what the roofline table uses,
    # but we calibrate on whatever mesh this cell runs on for consistency)
    period = _cal_period(cfg)
    g_frac = cfg.n_layers / period
    lm_mod.UNROLL = True
    try:
        c1 = _cost_triple(_lower_cell(
            _dc.replace(cfg, name=cfg.name + "-cal1", n_layers=period),
            shape_name, mesh, opt_kind=opt_kind))
        c2 = _cost_triple(_lower_cell(
            _dc.replace(cfg, name=cfg.name + "-cal2", n_layers=2 * period),
            shape_name, mesh, opt_kind=opt_kind))
    finally:
        lm_mod.UNROLL = False
    per_group = tuple(max(b - a, 0.0) for a, b in zip(c1, c2))
    flops, bytes_acc, coll_total = (
        a + (g_frac - 1.0) * d for a, d in zip(c1, per_group))

    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    coll["total_calibrated"] = coll_total
    terms = roofline(flops, bytes_acc, coll_total)

    # MODEL_FLOPS: 6*N*D for train (fwd+bwd), 2*N*D forward-only; decode D=batch tokens
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        model_flops = 6.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2.0 * n_active * shape.global_batch  # one token each
    model_flops_per_chip = model_flops / chips

    # peak HBM: args + temps + non-aliased outputs (donated buffers alias)
    hbm_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + max(mem.output_size_in_bytes - mem.alias_size_in_bytes, 0)
               ) / 2**30
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "status": "ok",
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_GiB": round(mem.argument_size_in_bytes / 2**30, 3),
            "output_GiB": round(mem.output_size_in_bytes / 2**30, 3),
            "temp_GiB": round(mem.temp_size_in_bytes / 2**30, 3),
            "alias_GiB": round(mem.alias_size_in_bytes / 2**30, 3),
            "total_GiB_per_chip": round(hbm_gib, 3),
            "fits_16GiB": bool(hbm_gib < 16.0),
        },
        "cost": {"flops_per_chip": flops, "bytes_per_chip": bytes_acc},
        "collectives": {k: round(v, 0) if isinstance(v, float) else v
                        for k, v in coll.items()},
        "roofline": {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s, "dominant": terms.dominant,
            "bound_s": terms.bound_s,
            "model_flops_per_chip": model_flops_per_chip,
            "useful_flops_ratio": (model_flops_per_chip / flops) if flops else 0.0,
            "roofline_fraction": (min(model_flops_per_chip / 197e12, terms.bound_s)
                                  / terms.bound_s) if terms.bound_s else 0.0,
        },
    }
    if verbose:
        print(json.dumps(result, indent=1))
        print(f"memory_analysis: {mem}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    for a in archs:
        shapes = applicable_shapes(get_config(a)) if (
            args.all or not args.shape) else [args.shape]
        for s in shapes:
            meshes = {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]
            for mp in meshes:
                cells.append((a, s, mp))

    for a, s, mp in cells:
        tag = f"{a}__{s}__{'multi' if mp else 'single'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag}")
            continue
        print(f"[run ] {tag}", flush=True)
        try:
            res = run_cell(a, s, mp, verbose=False)
        except Exception as e:  # noqa: BLE001 -- a failed cell is a bug report
            res = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": f"FAIL: {type(e).__name__}: {str(e)[:400]}"}
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(f"[done] {tag}: {res['status']}"
              + (f" dominant={res['roofline']['dominant']}"
                 f" fits={res['memory']['fits_16GiB']}"
                 if res["status"] == "ok" else ""), flush=True)


if __name__ == "__main__":
    main()
