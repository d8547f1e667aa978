#!/usr/bin/env python3
"""On-chip benchmark of the Kitsune reproduction: one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name under this directory:

    workloads/<cell>.json   driver, configuration, traffic, program options
                            and the limits that decide `correct`
    configs/<config>.json   the model at its published widths, the cut, and
                            the name of the plain reference it is checked
                            against
    reference/<name>.py     that reference: `top_shapes(a)`,
                            `layer_shapes(a, i)` (layer i's leaves, named as
                            the program names them below `blocks/sub<j>/`),
                            `train(seed, a, batches, hp, steps, quant=...)`,
                            `fp8` (the control's precision) and
                            `train_flops_per_token(a, seq)`
    traffic/<traffic>.json  parameters of the one traffic generator
    drivers/<driver>.py     `run(ctx) -> dict` (train)
    metrics/<metric>.py     `read(rec) -> float | None`, one per-layer metric
    peaks.json              peak FLOP/s and bytes/s by `device_kind`

A cell, configuration (with its reference), traffic mix or per-layer metric
is added by adding its files and its `BENCHMARK.json` entry; nothing here
names one.

The run needs a TPU: without one, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.  The last line of standard
output is one JSON object (`correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` also `breakdown`, and `checks` last: each number
compared with its limit).  Diagnostics go on the lines before it.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(CHECKOUT / "src"))

from common import Context, log  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference at the precision below the "
                         "configuration's in the program's place, and report "
                         "its readings against the limits (no window)")
    ap.add_argument("--fault", default=None,
                    help="plant one of the driver's named faults under the "
                         "timed path, to read what the checks make of it")
    return ap.parse_args(argv)


def device_check(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero off a TPU or short of
    chips.  Touches JAX, so it runs in the one process that holds them."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        sys.exit(f"run.py: no TPU (JAX platform {dev.platform!r}); the "
                 f"benchmark runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell needs {chips} TPU chips, found "
                 f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (or `$JAX_COMPILATION_CACHE_DIR`); every program is
    cached, so a second run of a cell compiles nothing."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries of BENCHMARK.json that this
    cell reports: those listing it, or, without a `workloads` key, those
    whose moved end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def run(args, *, root: Path = HERE, bench_path: Path | None = None,
        check_device: bool = True, fault: str | None = None) -> dict:
    """One run of one cell; returns the result object.  `root` is where the
    workload, configuration and traffic files are looked up, and `fault`
    plants one of the drivers' named faults under the timed path (both for
    the tests)."""
    ctx = Context.load(root, args.workload, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       control=args.control, fault=fault or args.fault,
                       t0=PROCESS_T0)
    device = (device_check(ctx.workload["chips"]) if check_device
              else {"platform": "cpu", "kind": "cpu", "count": 1})
    ctx.device = device
    if check_device:
        log(f"[setup] compilation cache: {enable_cache()}")
    bench = json.loads((bench_path or CHECKOUT / "BENCHMARK.json")
                       .read_text())
    ctx.peaks = ctx.peak_for(device["kind"])
    driver = load_module(HERE / "drivers" / f"{ctx.workload['driver']}.py",
                         f"driver_{ctx.workload['driver']}")
    out = driver.run(ctx)

    metrics = {}
    if args.control:
        pass                    # the control reports its readings only
    elif args.trace:
        for m in cell_metrics(bench, args.workload, "per_layer"):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(out["rec"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, args.workload, "end_to_end"):
            if m["name"] not in out["e2e"]:
                raise KeyError(f"driver gave no {m['name']} for "
                               f"{args.workload}")
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(device)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    if args.trace and out["rec"].get("trace"):
        tr = out["rec"]["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out["rec"].get("trace"):
        result["breakdown"] = out["rec"]["trace"]["breakdown"]
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> None:
    args = parse(argv)
    result = run(args)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
