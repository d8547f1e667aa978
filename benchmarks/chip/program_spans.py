"""The program's own host spans (`repro.spans`; `kitsune:<name>` in a
profiler trace), for the per-layer metrics that read them and for
`spans_report.py`.

`totals()` and `durations(name)` read the program's span table in this
process.  Where the program keeps none (a checkout from before it had
spans) they return nothing, and so do the metrics that read them.

`host_spans(trace_dir)` reads both kinds of host span from a trace: the
benchmark's (`bench:<name>`, named `<name>` as `trace_reduce` names them)
and the program's (kept as `kitsune:<name>`, with their arguments).
`attribute(devices, spans)` reads, over the benchmark's `window`:

  run_ms         durations of the `kitsune:run` spans (one per step)
  idle_s         device-idle seconds (the complement of the union of the
                 device's operations, averaged over devices)
  idle_in_run_s  idle seconds inside `kitsune:run` spans
  idle_by_span   idle seconds by the innermost span of either kind
  idle_gaps      the ten longest idle gaps, by the innermost span at the
                 middle of each
  program_host_s host seconds of the `kitsune:program` spans, by program
  modules        device programs launched, by XLA module name (the name
                 before its fingerprint), per step
  skew_ms        the device's clock against the host's: each `kitsune.`
                 module's start less its launch span's start (the device
                 runs a program only after its launch began, so a negative
                 value is clock skew), smallest and median over all
                 launches and over each step's first launch
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import statistics

import trace_reduce

PROGRAM_PREFIX = "kitsune:"
RUN = PROGRAM_PREFIX + "run"
LAUNCH = PROGRAM_PREFIX + "program"
OUTSIDE = "outside spans"


def totals() -> dict:
    """The program's span table: name -> calls, seconds, self_seconds."""
    try:
        from repro import spans
    except ImportError:
        return {}
    return spans.totals()


def durations(name: str) -> list:
    try:
        from repro import spans
    except ImportError:
        return []
    return spans.durations(name)


def host_spans(trace_dir) -> list:
    """[name, start_ns, end_ns, args] of every benchmark and program span
    in the trace under `trace_dir`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    bench = trace_reduce.SPAN_PREFIX
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append([ev.name, ev.start_ns, ev.end_ns,
                                {k: v for k, v in ev.stats}])
                elif ev.name.startswith(bench):
                    out.append([ev.name[len(bench):], ev.start_ns,
                                ev.end_ns, {}])
    return out


def _innermost(spans: list, w0: float, w1: float) -> list:
    """[start, end, name] segments tiling [w0, w1), each named by the
    innermost span over it: of the spans covering it, the one that began
    last (the shorter on a tie)."""
    order = sorted((max(s, w0), min(e, w1), name) for name, s, e, _ in spans
                   if min(e, w1) > max(s, w0))
    cuts = sorted({w0, w1, *(s for s, _, _ in order),
                   *(e for _, e, _ in order)})
    heap: list = []
    segs: list = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][0] <= a:
            s, e, name = order[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][2] if heap else OUTSIDE
        if segs and segs[-1][2] == name and segs[-1][1] == a:
            segs[-1][1] = b
        else:
            segs.append([a, b, name])
    return segs


def _overlap(a: list, b: list) -> list:
    """[start, end, b's label] of the intersections of two sorted lists of
    disjoint intervals; `b`'s entries may carry a label third."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e, b[j][2] if len(b[j]) > 2 else None])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _label_at(segs: list, t: float) -> str:
    k = bisect.bisect_right([s for s, _, _ in segs], t) - 1
    return segs[k][2] if 0 <= k and t < segs[k][1] else OUTSIDE


def attribute(devices: dict, spans: list) -> dict:
    """The numbers listed in the module docstring, from `trace_reduce.load`'s
    `devices` and `host_spans`' spans."""
    wins = [(s, e) for name, s, e, _ in spans if name == trace_reduce.WINDOW]
    if not wins:
        raise ValueError("the trace holds no `window` span")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    inside = [sp for sp in spans
              if sp[0] != trace_reduce.WINDOW and sp[2] > w0 and sp[1] < w1]
    segs = _innermost(inside, w0, w1)
    runs = sorted([s, e] for name, s, e, _ in inside
                  if name == RUN and w0 <= s and e <= w1)
    run_union = trace_reduce._union(runs)
    by_span: dict = {}
    idle_ns = in_run = 0.0
    gaps: list = []
    for dev in devices.values():
        iv = [(max(s, w0), min(e, w1)) for _, _, s, e in dev["ops"]
              if min(e, w1) > max(s, w0)]
        edges = [w0] + [x for iv_ in trace_reduce._union(iv)
                        for x in iv_] + [w1]
        idle = [[edges[k], edges[k + 1]] for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        idle_ns += sum(e - s for s, e in idle)
        for s, e in idle:
            gaps.append((e - s, _label_at(segs, (s + e) / 2)))
        for s, e, name in _overlap(idle, segs):
            by_span[name] = by_span.get(name, 0.0) + (e - s)
        in_run += sum(e - s for s, e, _ in _overlap(idle, run_union))
    n_dev = max(len(devices), 1)
    host_s: dict = {}
    for name, s, e, args in inside:
        if name == LAUNCH and w0 <= s and e <= w1:
            p = str(args.get("program"))
            host_s[p] = host_s.get(p, 0.0) + (e - s) / 1e9
    steps = max(len(runs), 1)
    first = next(iter(devices.values()), {"modules": []})
    modules: dict = {}
    for name, s, _ in first["modules"]:
        if w0 <= s < w1:
            key = name.split("(")[0]
            modules[key] = modules.get(key, 0) + 1
    return {
        "run_ms": [(e - s) / 1e6 for s, e in runs],
        "idle_s": idle_ns / n_dev / 1e9,
        "idle_in_run_s": in_run / n_dev / 1e9,
        "idle_by_span": {k: v / n_dev / 1e9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[name, ns / 1e9] for ns, name in
                      sorted(gaps, key=lambda g: -g[0])[:10]],
        "program_host_s": dict(sorted(host_s.items(),
                                      key=lambda kv: -kv[1])),
        "modules": {k: v / steps for k, v in sorted(
            modules.items(), key=lambda kv: -kv[1])},
        "skew_ms": _skew(inside, runs, first["modules"], w0, w1),
    }


def _skew(inside: list, runs: list, modules: list, w0: float,
          w1: float) -> dict:
    """Each kitsune module's start less its launch span's, in the window.

    The device runs launches in their order, but the trace may miss a
    module or two at either end of the window (device tracing starts late;
    a last program runs after the window closes).  So the two sequences
    are aligned at the offset under which most module names carry their
    launch's program name."""
    launches = sorted((s, str(args.get("program")))
                      for name, s, e, args in inside
                      if name == LAUNCH and w0 <= s < w1)
    mods = sorted((s, name) for name, s, _ in modules
                  if w0 <= s < w1 and "kitsune." in name)
    if not launches or not mods:
        return {}

    def pairs(o):
        return [(launches[j + o], mods[j]) for j in range(len(mods))
                if 0 <= j + o < len(launches)]

    def named(ps):
        return sum(1 for (_, p), (_, m) in ps if f"kitsune.{p}(" in m)

    reach = len({p for _, p in launches}) + 1
    best = max(range(-reach, reach + 1),
               key=lambda o: (named(pairs(o)), -abs(o)))
    ps = pairs(best)
    if not ps:
        return {}
    diffs = [(ms - hs) / 1e6 for (hs, _), (ms, _) in ps]
    starts = [s for s, _ in runs]
    step_of = [bisect.bisect_right(starts, hs) - 1 for (hs, _), _ in ps]
    firsts = [d for k, d in enumerate(diffs)
              if step_of[k] >= 0 and (k == 0 or step_of[k - 1] != step_of[k])]
    return {"launches": len(ps), "min": min(diffs),
            "median": statistics.median(diffs),
            "step_first_min": min(firsts) if firsts else None,
            "step_first_median": (statistics.median(firsts) if firsts
                                  else None),
            "named_share": named(ps) / len(ps)}
