"""Named host spans over the compiler and the executor.

`span(name, **args)` marks one piece of host work.  It enters
`jax.profiler.TraceAnnotation("kitsune:<name>", **args)`, so a running
profiler records the span on its host timeline beside the device's
operations, and it adds the piece's wall-clock seconds to a process-wide
table under `name`: calls, seconds, and self seconds (the span's seconds
less those of the spans opened inside it on the same thread).

    with span("pass/select"):
        ...
    totals()["pass/select"]   # {"calls": 1, "seconds": ..., "self_seconds": ...}

`durations(name)` gives the latest seconds of one name (the last `KEEP`
calls), `reset()` clears the table.  Spans are always on: with no profiler
running, one costs about two microseconds of host time.

The span names, where they are opened, and the argument each carries:

    pass/trace, pass/<name>   core/compiler.py: pass 0 and each pass
    autotune                  kernels/autotune.py: one uncached tile search
                              (kernel, candidates)
    verdict_measure           core/lower.py: one lowering microbenchmark
                              (kernel)
    compile_program           core/executor.py: one plan program lowered
                              and compiled, or loaded from JAX's cache
                              (program)
    run                       core/compiler.py: one call of a TracedApp
                              (call: its index)
      feeds                   arguments flattened, plan chosen, buffers
                              filled
      program                 one executable launched (program)
      inline                  one free op evaluated eagerly (op: its kind)
      outputs                 results put back into the caller's structure
"""
from __future__ import annotations

import threading
import time
from collections import deque

import jax

PREFIX = "kitsune:"
KEEP = 4096                 # durations kept per name

_lock = threading.Lock()
_rows: dict[str, list] = {}     # name -> [calls, seconds, self, durations]
_local = threading.local()


class span:
    """One named span; after it closes, `seconds` holds its duration."""

    __slots__ = ("name", "seconds", "_ann", "_t0", "_inner")

    def __init__(self, name: str, **args):
        self.name = name
        self.seconds = 0.0
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._inner = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self.seconds = dt
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._inner += dt
        with _lock:
            row = _rows.get(self.name)
            if row is None:
                row = _rows[self.name] = [0, 0.0, 0.0, deque(maxlen=KEEP)]
            row[0] += 1
            row[1] += dt
            row[2] += dt - self._inner
            row[3].append(dt)
        self._ann.__exit__(*exc)
        return False


def totals() -> dict[str, dict]:
    """A snapshot of the table: name -> calls, seconds, self_seconds."""
    with _lock:
        return {name: {"calls": r[0], "seconds": r[1], "self_seconds": r[2]}
                for name, r in _rows.items()}


def durations(name: str) -> list[float]:
    """Seconds of the latest calls of `name` (at most `KEEP`), oldest
    first."""
    with _lock:
        row = _rows.get(name)
        return list(row[3]) if row is not None else []


def reset() -> None:
    with _lock:
        _rows.clear()
