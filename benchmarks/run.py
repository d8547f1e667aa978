"""Benchmark driver: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines.

``--smoke`` runs a tiny-shape subset (apps e2e/coverage + two traced
config-zoo architectures) and writes the results as JSON -- the CI artifact
that accumulates a BENCH_*.json trajectory across commits.  Since schema 4
the smoke run also REGRESSION-CHECKS lowering: per measured app,
`kitsune.us_per_call` must not exceed `kitsune_nolower.us_per_call` beyond
a noise tolerance (the cost/measurement verdicts in core/lower.py exist to
guarantee this); violations print a diff table and exit nonzero.  Since
schema 6 it also gates structural dedupe: repeated-layer / microbatch
workloads must compile exactly ONE executable per unique program structure
(bench_e2e.dedupe_smoke + check_dedupe_gate), bitwise-equal to the
dedupe-off compile.  Since schema 7 it also gates the paged-attention tick
data path: the block-table-native mode must stay bitwise-equal to its
gather oracle, move <= half the gather path's per-tick KV bytes, and run
no slower than gather beyond tolerance (bench_serve.paged_attention_modes
+ check_paged_gate; bytes table in the BENCH_paged.md artifact)."""
from __future__ import annotations

import json
import sys
import time
import traceback

# Noise tolerance for the lowering regression gate: tiny-instance CPU
# timings jitter, so "no slower" means within max(rel_tol fraction,
# abs_tol_us microseconds) of the unlowered wall-clock.
LOWERING_REL_TOL = 0.25
LOWERING_ABS_TOL_US = 30.0


def check_lowering_regressions(apps_measured: dict,
                               rel_tol: float = LOWERING_REL_TOL,
                               abs_tol_us: float = LOWERING_ABS_TOL_US,
                               ) -> dict:
    """Per-app lowering wall-clock gate over measured_e2e rows.

    Returns {"violations": [...], "table": [...], "rel_tol", "abs_tol_us"};
    a violation row means lowering made the app slower than the tolerance
    allows -- the verdict mechanism failed to decline an unprofitable site."""
    table, violations = [], []
    for name, row in sorted(apps_measured.items()):
        if "kitsune" not in row or "kitsune_nolower" not in row:
            continue
        kit = row["kitsune"]["us_per_call"]
        nol = row["kitsune_nolower"]["us_per_call"]
        limit = nol * (1.0 + rel_tol) + abs_tol_us
        entry = {"app": name, "kitsune_us": round(kit, 1),
                 "nolower_us": round(nol, 1), "limit_us": round(limit, 1),
                 "ok": kit <= limit}
        table.append(entry)
        if not entry["ok"]:
            violations.append(entry)
    return {"violations": violations, "table": table,
            "rel_tol": rel_tol, "abs_tol_us": abs_tol_us}


def check_paged_gate(pa: dict, rel_tol: float = LOWERING_REL_TOL,
                     abs_tol_us: float = LOWERING_ABS_TOL_US) -> dict:
    """Paged-attention tick-data-path gate over `bench_serve.
    paged_attention_modes` rows (schema 7).

    Violations: (a) the two modes' tokens are not bitwise identical (the
    native path diverged from its gather oracle), (b) native moves more
    than HALF the gather path's per-tick KV bytes (the >= 2x traffic
    reduction the block-table-native kernel exists to deliver), or (c)
    native per-token wall-clock exceeds gather beyond the same noise
    tolerance the lowering gate uses."""
    g, n = pa["gather"], pa["native"]
    g_us = g["wall_s"] / max(g["tokens"], 1) * 1e6
    n_us = n["wall_s"] / max(n["tokens"], 1) * 1e6
    limit_us = g_us * (1.0 + rel_tol) + abs_tol_us
    checks = [
        {"check": "bitwise_equal", "ok": bool(pa["bitwise_equal"]),
         "detail": f"bitwise={pa['bitwise_equal']}"},
        {"check": "kv_bytes_2x", "ok": 2 * n["kv_bytes_per_tick"]
                                       <= g["kv_bytes_per_tick"],
         "detail": f"native={n['kv_bytes_per_tick']:.0f}B/tick "
                   f"gather={g['kv_bytes_per_tick']:.0f}B/tick "
                   f"reduction={pa['bytes_reduction']:.2f}x"},
        {"check": "wall_clock", "ok": n_us <= limit_us,
         "detail": f"native={n_us:.1f}us/tok gather={g_us:.1f}us/tok "
                   f"limit={limit_us:.1f}us/tok"},
    ]
    return {"violations": [c for c in checks if not c["ok"]],
            "table": checks, "rel_tol": rel_tol, "abs_tol_us": abs_tol_us}


def _paged_table_md(pa: dict, check: dict) -> str:
    """Markdown bytes-moved table (BENCH_paged.md CI artifact)."""
    lines = ["# Paged-attention tick data path (smoke run)", "",
             "| mode | tok/s | ticks | KV bytes/tick | us/token |",
             "|---|---|---|---|---|"]
    for mode in ("gather", "native"):
        r = pa[mode]
        us = r["wall_s"] / max(r["tokens"], 1) * 1e6
        lines.append(f"| {mode} | {r['tok_s']:.1f} | {r['ticks']} "
                     f"| {r['kv_bytes_per_tick']:.0f} | {us:.1f} |")
    lines += ["", f"KV bytes reduction: **{pa['bytes_reduction']:.2f}x** "
                  f"(gate: >= 2x); bitwise equal: "
                  f"**{pa['bitwise_equal']}**", "", "## Gate", ""]
    for c in check["table"]:
        lines.append(f"- {'ok' if c['ok'] else 'VIOLATION'} "
                     f"`{c['check']}`: {c['detail']}")
    return "\n".join(lines) + "\n"


def check_dedupe_gate(dedupe_rows: dict) -> dict:
    """Structural-dedupe gate over `bench_e2e.dedupe_smoke` rows.

    A case violates when (a) dedupe-on compiled MORE than one executable per
    unique program structure (`executables_on > n_classes`), or (b) sharing
    changed a result (`bitwise_equal` false), or (c) a case whose program
    list repeats structurally (`expect_sharing`, e.g. the MoE 2x-layer graph
    or the unrolled microbatch loop) shows no sharing (`n_classes ==
    n_programs`) -- the canonical identity regressed."""
    table, violations = [], []
    for name, r in sorted(dedupe_rows.items()):
        ok = (r["executables_on"] <= r["n_classes"]
              and r["bitwise_equal"]
              and (not r.get("expect_sharing")
                   or r["n_classes"] < r["n_programs"]))
        entry = {"case": name, "executables_on": r["executables_on"],
                 "n_classes": r["n_classes"], "n_programs": r["n_programs"],
                 "hit_rate": r["hit_rate"],
                 "bitwise_equal": r["bitwise_equal"], "ok": ok}
        table.append(entry)
        if not ok:
            violations.append(entry)
    return {"violations": violations, "table": table}


def _verdict_table_md(apps_measured: dict) -> str:
    """Markdown per-site verdict table (BENCH_verdicts.md CI artifact)."""
    lines = ["# Lowering verdicts (smoke run)", "",
             "| app | pipeline | kernel | decision | source | "
             "est k/c (us) | meas k/c (us) |",
             "|---|---|---|---|---|---|---|"]

    def fmt(a, b):
        if a is None and b is None:
            return "-"
        f = lambda x: f"{x:.1f}" if x is not None else "?"
        return f"{f(a)} / {f(b)}"

    for name, row in sorted(apps_measured.items()):
        for v in row.get("lowering_verdicts", []):
            lines.append(
                f"| {name} | {v['pipeline']} | {v['kernel']} "
                f"| {v['decision']} | {v['source']} "
                f"| {fmt(v['est_kernel_us'], v['est_closure_us'])} "
                f"| {fmt(v['meas_kernel_us'], v['meas_closure_us'])} |")
    return "\n".join(lines) + "\n"


def _print_check(check: dict) -> None:
    print("# lowering regression gate "
          f"(rel_tol={check['rel_tol']}, abs_tol_us={check['abs_tol_us']}):")
    for e in check["table"]:
        mark = "ok " if e["ok"] else "REGRESSION"
        print(f"#   {mark} {e['app']}: kitsune={e['kitsune_us']}us "
              f"nolower={e['nolower_us']}us limit={e['limit_us']}us")


def smoke(out_path: str = "BENCH_smoke.json") -> dict:
    from . import bench_coverage, bench_dispatch, bench_e2e, bench_serve
    zoo_names = ["gemma3-1b", "qwen1.5-32b"]
    t0 = time.time()
    gm_i, gm_t = bench_e2e.main(csv=False)
    apps_cov = bench_coverage.main(csv=False)
    # one trace+compile per arch (bench_e2e.zoo_app memo); the e2e ratios
    # and the coverage axis both read the same compiled artifact
    hw = bench_e2e.HW
    zoo_e2e = bench_e2e.zoo_e2e(zoo_names, csv=False)
    zoo_cov = {}
    for name in zoo_names:
        app, _, _ = bench_e2e.zoo_app(name)
        bsp = app.estimate(hw, "bsp")
        kit = app.estimate(hw, "kitsune")
        grouped, total = app.selection.coverage()
        zoo_cov[name] = {
            "ops": total, "grouped": grouped,
            "coverage": grouped / max(total, 1),
            "traffic_red_kitsune":
                1 - kit.dram_bytes / max(bsp.dram_bytes, 1)}
    dispatch = bench_dispatch.main(csv=False, iters=200)
    apps_measured = bench_e2e.measured_e2e(csv=False, iters=5)
    # training axis: full fwd+bwd+update steps through training
    # ExecutionPlans (params donated), measured kitsune-vs-bsp wall-clock
    # and XLA boundary traffic (see EXPERIMENTS.md for the schema)
    apps_train = bench_e2e.measured_train_e2e(csv=False, iters=5)
    # serving axis: paged KV engine vs the legacy contiguous engine, same
    # request stream; tracks tokens/s, tick p50/p99, and the concurrency
    # headroom paging buys (peak_active vs legacy slot count).  The chaos
    # sub-section replays the workload under a scripted multi-site fault
    # schedule and asserts the fault-tolerance contract (only culpable
    # requests fail, survivors bitwise) while recording recovery ticks.
    serve = bench_serve.main(csv=False)
    # structural-dedupe axis: repeated-layer / microbatch workloads compiled
    # with the dedupe pass off vs on -- executable counts, hit-rate, and the
    # trace+compile+first-run wall-clock reduction, outputs checked bitwise
    dedupe = bench_e2e.dedupe_smoke(csv=False)
    check = check_lowering_regressions(apps_measured)
    dedupe_check = check_dedupe_gate(dedupe)
    paged_check = check_paged_gate(serve["paged_attention"])
    calibration = bench_e2e.calibration_from_measured(apps_measured)
    results = {
        "schema": 7,
        "kind": "smoke",
        "unix_time": time.time(),
        "wall_s": time.time() - t0,
        "e2e_geomean": {"inference": gm_i, "training": gm_t},
        "apps_coverage": {
            name: r["inference"] for name, r in apps_cov.items()},
        "apps_measured": apps_measured,
        "apps_train_measured": apps_train,
        "zoo_e2e": zoo_e2e,
        "zoo_coverage": zoo_cov,
        "dispatch_overhead": dispatch,
        "serve": serve,
        "hw_calibration": calibration,
        "lowering_check": check,
        "dedupe": dedupe,
        "dedupe_check": dedupe_check,
        "paged_check": paged_check,
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    stem = out_path.rsplit(".", 1)[0]
    verdict_path = stem.replace("_smoke", "") + "_verdicts.md"
    with open(verdict_path, "w") as f:
        f.write(_verdict_table_md(apps_measured))
    paged_path = stem.replace("_smoke", "") + "_paged.md"
    with open(paged_path, "w") as f:
        f.write(_paged_table_md(serve["paged_attention"], paged_check))
    train_red = {n: round(r["traffic_reduction"], 2)
                 for n, r in apps_train.items()}
    print(f"# smoke results -> {out_path} "
          f"(e2e geomean inf={gm_i:.2f} train={gm_t:.2f}, "
          f"zoo={list(zoo_e2e)}, train_traffic_red={train_red}, "
          f"dispatch_overhead_speedup={dispatch['overhead_speedup']:.1f}x, "
          f"serve_paged={serve['paged']['tok_s']:.0f}tok/s "
          f"{serve['speedup']:.2f}x legacy, "
          f"kv_bytes_red={serve['paged_attention']['bytes_reduction']:.2f}x, "
          f"chaos_recovery={serve['chaos']['recovery_ticks_mean']:.1f}ticks "
          f"failed={serve['chaos']['failed']})")
    print(f"# paged table -> {paged_path}")
    print("# paged-attention gate (native bitwise, >=2x KV bytes, "
          "no slower):")
    for c in paged_check["table"]:
        mark = "ok " if c["ok"] else "VIOLATION"
        print(f"#   {mark} {c['check']}: {c['detail']}")
    print(f"# verdict table -> {verdict_path} "
          f"(calibrated eff={calibration['eff']:.2e}, "
          f"launch_s={calibration['launch_s']:.2e})")
    _print_check(check)
    print("# dedupe gate (one executable per unique program structure):")
    for e in dedupe_check["table"]:
        mark = "ok " if e["ok"] else "VIOLATION"
        print(f"#   {mark} {e['case']}: exes={e['executables_on']} "
              f"classes={e['n_classes']} programs={e['n_programs']} "
              f"hit={e['hit_rate']:.2f} bitwise={e['bitwise_equal']}")
    return results


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape subset, results written as JSON")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="JSON path for --smoke results")
    ns = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if ns.smoke:
        results = smoke(ns.out)
        violations = results["lowering_check"]["violations"]
        if violations:
            print("# LOWERING REGRESSIONS (kitsune slower than "
                  "kitsune_nolower beyond tolerance):")
            for e in violations:
                print(f"#   {e['app']}: kitsune={e['kitsune_us']}us > "
                      f"limit={e['limit_us']}us "
                      f"(nolower={e['nolower_us']}us)")
            sys.exit(1)
        dedupe_violations = results["dedupe_check"]["violations"]
        if dedupe_violations:
            print("# DEDUPE VIOLATIONS (more than one executable per unique "
                  "program structure, lost sharing, or bitwise drift):")
            for e in dedupe_violations:
                print(f"#   {e['case']}: exes={e['executables_on']} "
                      f"classes={e['n_classes']} programs={e['n_programs']} "
                      f"bitwise={e['bitwise_equal']}")
            sys.exit(1)
        paged_violations = results["paged_check"]["violations"]
        if paged_violations:
            print("# PAGED-ATTENTION VIOLATIONS (native diverged from the "
                  "gather oracle, moved > half the gather KV bytes, or ran "
                  "slower beyond tolerance):")
            for c in paged_violations:
                print(f"#   {c['check']}: {c['detail']}")
            sys.exit(1)
        return
    from . import (bench_coverage, bench_dispatch, bench_e2e, bench_kernels,
                   bench_queue, bench_roofline, bench_sensitivity,
                   bench_serve, bench_subgraph, bench_utilization)
    sections = [
        ("Fig5_queue_bandwidth", bench_queue.main),
        ("Table2_coverage_traffic", bench_coverage.main),
        ("Fig10_12_subgraph_speedups", bench_subgraph.main),
        ("Fig11_14_e2e_speedups", bench_e2e.main),
        ("Fig10_sensitivity", bench_sensitivity.main),
        ("Fig3_13_utilization", bench_utilization.main),
        ("kernel_benchmarks", bench_kernels.main),
        ("dispatch_overhead", bench_dispatch.main),
        ("serving_engines", bench_serve.main),
        ("roofline_table", bench_roofline.main),
    ]
    failed = []
    for name, fn in sections:
        print(f"# === {name} ===")
        try:
            fn()
        except Exception:  # noqa: BLE001 -- report, keep going
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED sections: {failed}")
        sys.exit(1)
    print("# all benchmark sections passed")


if __name__ == "__main__":
    main()
