#!/usr/bin/env python3
"""Run one cell as run.py does, and report what the program's own spans
and its caches saw: where set-up went, which tiles the process chose, and,
traced, what the host was doing in each idle gap of the device.

    python3 benchmarks/chip/spans_report.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The run is run.py's, the same window, checks and result line, which comes
last.  Before it:

  [span]   each row of the program's span table (`repro.spans.totals()`)
  [tune]   each tile search: kernel, operand shapes, winning blocks, its
           microseconds and the candidates the compiler refused
  [verdict] each lowering verdict: kernel, operand shapes, decision, the
           tier that decided and its microseconds

and with --trace 1, from the trace before `drivers/train.py` reduces and
deletes it (`program_spans.attribute`):

  [idle]   idle seconds by the innermost span, benchmark's or program's
  [gap]    the ten longest idle gaps, named the same way
  [host]   host seconds of each program's launch spans in the window
  [module] device programs per step, by XLA module name
  [attrib] run_ms median, idle in `kitsune:run`, and the clock skew

`--save <file.json.gz>` also writes the trace's device intervals and host
spans there, for `program_spans.attribute` to read again off the chip.
"""
from __future__ import annotations

import argparse
import gzip
import json
import statistics
import sys
import traceback

import run as R  # first: run.py's clock starts at its import
import program_spans
import trace_reduce
from common import log


def _shapes(sig) -> str:
    """Operand shapes of a site from its shape signature."""
    return " ".join("x".join(str(d) for d in shape) + f":{dt}"
                    for shape, dt in sig[2])


def log_caches() -> None:
    from repro.core.executor import verdict_cache
    from repro.kernels.autotune import tune_cache
    for key, choice in tune_cache().items():
        sig = key[1]
        blocks = {k: v for k, v in choice.items()
                  if k not in ("us", "refused")}
        log(f"[tune] {sig[0]} {_shapes(sig)} blocks {blocks} us "
            f"{choice.get('us')!r} refused {list(choice.get('refused', ()))}")
    for key, v in verdict_cache().items():
        sig = key[2]
        log(f"[verdict] {sig[0]} {_shapes(sig)} {v.decision} by {v.source} "
            f"est {v.est_kernel_us!r}/{v.est_closure_us!r} us measured "
            f"{v.meas_kernel_us!r}/{v.meas_closure_us!r} us")


def log_attribution(a: dict) -> None:
    for name, s in a["idle_by_span"].items():
        log(f"[idle] {name} {s!r} s")
    for name, s in a["idle_gaps"]:
        log(f"[gap] {name} {s!r} s")
    for name, s in a["program_host_s"].items():
        log(f"[host] {name} {s!r} s")
    for name, n in a["modules"].items():
        log(f"[module] {name} {n!r} per step")
    runs = a["run_ms"]
    log(f"[attrib] steps {len(runs)} run_ms median "
        f"{statistics.median(runs) if runs else None!r} idle_s "
        f"{a['idle_s']!r} idle_in_run_s {a['idle_in_run_s']!r} skew_ms "
        f"{json.dumps(a['skew_ms'])}")


def save(path: str, devices: dict, spans: list) -> None:
    """Device op intervals, modules and host spans, as `attribute` reads
    them (op names and labels left out)."""
    slim = {k: {"ops": [["", "", s, e] for _, _, s, e in d["ops"]],
                "modules": d["modules"]} for k, d in devices.items()}
    with gzip.open(path, "wt") as f:
        json.dump({"devices": slim, "spans": spans}, f)


def main(argv=None, **run_kw) -> None:
    """`run_kw` goes to `run.run` (the tests run a cell off the chip)."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--save", default=None)
    extra, argv = ap.parse_known_args(argv)
    args = R.parse(argv)
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_attribute(trace_dir):
        # a fault here costs the report, not the run it reports on
        try:
            devices = trace_reduce.load(trace_dir)["devices"]
            spans = program_spans.host_spans(trace_dir)
            if extra.save:
                save(extra.save, devices, spans)
            log_attribution(program_spans.attribute(devices, spans))
        except Exception:  # noqa: BLE001 - reported, the run goes on
            log("[attrib] failed: " + traceback.format_exc()
                .replace("\n", " | "))
        return reduce_dir(trace_dir)

    trace_reduce.reduce_dir = reduce_and_attribute
    try:
        result = R.run(args, **run_kw)
    finally:
        trace_reduce.reduce_dir = reduce_dir
    for name, row in sorted(program_spans.totals().items()):
        log(f"[span] {name} calls {row['calls']} seconds "
            f"{row['seconds']!r} self {row['self_seconds']!r}")
    log_caches()
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
