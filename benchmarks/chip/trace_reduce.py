"""Reduction of a JAX profiler trace to the benchmark's device numbers.

`load(dir)` reads the `.xplane.pb` under a trace directory into plain
events; `reduce(events)` turns them into

  window_s, busy_s     the window (the benchmark's `window` host span) and
                       the union of the intervals in which an operation ran
                       on a device, averaged over the devices
  ops                  per operation label: [calls, seconds]
  programs             device program launches (XLA Modules) in the window
  breakdown            the ten device operations that took most time (by
                       `short_label`), and the ten longest idle gaps by the
                       innermost benchmark host span at the middle of each

An operation's label is its HLO text as the profiler records it.  The
program names no kernel, so `signature(label)` reads a call's output and
operand shapes from it, and a kernel is found by the shapes of its call.

Device and host timestamps come from two clocks; on a TPU v5e they were
seen about a millisecond apart, small beside the window and the gaps that
the breakdown reports.

Both halves work on plain lists, so the reduction is checked on a small
recorded trace without a chip (tests/test_trace_reduce.py).
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench:"
WINDOW = "window"
DEVICE_PREFIX = "/device:TPU:"
LABEL_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details")


_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def _shapes(text: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def signature(label: str) -> dict | None:
    """Output and operand shapes of a custom call from its HLO text:
    {"target", "outputs": [(dtype, dims)], "operands": [(dtype, dims)]},
    or None for any other operation."""
    m = re.match(r"\s*%?\S+ = (.*?) custom-call\((.*?)\), "
                 r"custom_call_target=\"([^\"]+)\"", label)
    if not m:
        return None
    return {"target": m.group(3), "outputs": _shapes(m.group(1)),
            "operands": _shapes(m.group(2))}


def short_label(label: str, limit: int = 160) -> str:
    """An operation's name, output types and opcode, without layouts or
    operands: `%tpu_custom_call.7 = (f32[5120,17920], ...) custom-call`."""
    m = re.match(r"\s*(%?\S+ = .*?) ([a-z][\w-]*)\(", label)
    text = f"{m.group(1)} {m.group(2)}" if m else label
    while True:
        stripped = _LAYOUT.sub("", text)
        if stripped == text:
            break
        text = stripped
    return text if len(text) <= limit else text[:limit - 3] + "..."


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if k in LABEL_STATS and isinstance(v, str):
            out[k] = v
    return out


def load(trace_dir) -> dict:
    """Device operations and programs per device, and the benchmark's host
    spans, as [name, start_ns, end_ns] (operations carry a label too)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        st = _stats(ev)
                        label = st.get("long_name") or st.get("hlo_op") \
                            or ev.name
                        dev["ops"].append([ev.name, label, ev.start_ns,
                                           ev.end_ns])
                elif line.name == "XLA Modules":
                    dev["modules"].extend([ev.name, ev.start_ns, ev.end_ns]
                                          for ev in line.events)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [ev.name[len(SPAN_PREFIX):], ev.start_ns, ev.end_ns]
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covering(host: list, s: float, e: float) -> str:
    """The innermost benchmark span at the middle of the gap [s, e]."""
    mid = (s + e) / 2
    inside = [(he - hs, name) for name, hs, he in host
              if name != WINDOW and hs <= mid < he]
    return min(inside)[1] if inside else "outside spans"


def reduce(events: dict) -> dict:
    wins = [(s, e) for name, s, e in events["host"] if name == WINDOW]
    if not wins:
        raise ValueError("the trace holds no `window` span")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    window_ns = w1 - w0
    host = [h for h in events["host"] if h[2] > w0 and h[1] < w1]
    busy, ops, programs, gaps = [], {}, 0, []
    devices = events["devices"]
    for dev in devices.values():
        iv = []
        for name, label, s, e in dev["ops"]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            calls, ns = ops.get(label, (0, 0))
            ops[label] = (calls + 1, ns + (e - s))
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        programs += sum(1 for _, s, _ in dev["modules"] if w0 <= s < w1)
        edges = [w0] + [x for iv_ in merged for x in iv_] + [w1]
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e > s:
                gaps.append((e - s, _covering(host, s, e)))
    n_dev = max(len(devices), 1)
    busy_s = sum(busy) / n_dev / 1e9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "ops": {k: [c, ns / 1e9] for k, (c, ns) in ops.items()},
        "programs": programs / n_dev,
        "breakdown": {
            "device_ops": [[short_label(k), ns / 1e9 / n_dev]
                           for k, (_, ns) in top_ops],
            "idle_gaps": [[name, ns / 1e9] for ns, name in top_gaps]},
    }


def reduce_dir(trace_dir) -> dict:
    """Load and reduce a trace directory, then delete it: traces are large
    and every number the benchmark needs is in the reduction."""
    import shutil
    try:
        return reduce(load(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def kernel_calls(red: dict, match) -> list[tuple[dict, int, float]]:
    """(signature, calls, seconds) of every custom call whose signature
    `match(sig)` accepts."""
    out = []
    for label, (c, s) in red["ops"].items():
        sig = signature(label)
        if sig is not None and match(sig):
            out.append((sig, c, s))
    return out


def call_bytes(sig: dict) -> int:
    """Bytes a call reads and writes: its operands and outputs, once."""
    n = 0
    for dt, dims in sig["operands"] + sig["outputs"]:
        size = DTYPE_BYTES[dt]
        for x in dims:
            size *= x
        n += size
    return n


def log_ops(red: dict, top: int = 25) -> None:
    """Diagnostics: the operations that took most device time."""
    for label, (c, s) in sorted(red["ops"].items(),
                                key=lambda kv: -kv[1][1])[:top]:
        print(f"[trace] {s:.6f}s {c} calls {short_label(label)}", flush=True)
    print(f"[trace] busy {red['busy_s']:.6f}s of {red['window_s']:.6f}s, "
          f"{red['programs']} programs launched", flush=True)
