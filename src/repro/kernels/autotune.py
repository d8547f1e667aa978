"""Block-size autotuning for the Pallas kernels.

The kernels historically ran hardcoded tiles (block_m=128 / block_h=512,
block_q=block_k=128, block_s=256, block_rows=128).  The right tile depends
on the shape and the platform, so each kernel now exposes a small candidate
grid (`tile_candidates` in fused_mlp.py / flash_attention.py /
queue_reduce.py, already filtered to exact divisors of the shape) and the
lowering pass searches it at first-build: every candidate is compiled and
timed on synthesized feed-shaped inputs, the fastest wins, and the choice is
cached process-wide by (kernel, shape signature, platform) so later builds
of the same site pay nothing.

Timing helper `time_fn` is shared with the lowering verdict microbenchmark
(core/lower.py): one warmup call that also absorbs compilation, then the min
over a couple of timed calls with `block_until_ready`.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Iterable

import jax

from ..spans import span


def time_fn(fn: Callable, args: tuple, iters: int = 2) -> float:
    """Best-of-`iters` wall-clock seconds of fn(*args); the untimed first
    call absorbs jit compilation."""
    r = fn(*args)
    jax.block_until_ready(r)
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        best = min(best, time.perf_counter() - t0)
    return best


class TuneCache:
    """Process-wide (kernel, shape, platform) -> chosen-candidate store."""

    def __init__(self):
        self._store: dict[Any, dict] = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def __len__(self):
        with self._lock:
            return len(self._store)

    def get(self, key):
        with self._lock:
            v = self._store.get(key)
            if v is None:
                self.misses += 1
            else:
                self.hits += 1
            return v

    def put(self, key, choice: dict) -> None:
        with self._lock:
            self._store[key] = choice

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"size": len(self._store), "hits": self.hits,
                    "misses": self.misses}

    def items(self) -> list[tuple[Any, dict]]:
        """(key, choice) of every cached site."""
        with self._lock:
            return list(self._store.items())

    def clear(self) -> None:
        with self._lock:
            self._store.clear()


_TUNE = TuneCache()


def tune_cache() -> TuneCache:
    return _TUNE


def autotune(key: tuple, candidates: Iterable[dict],
             build: Callable[[dict], Callable], args: tuple,
             iters: int = 2, kernel: str = "") -> dict:
    """Pick the fastest candidate for one kernel site.

    `build(candidate)` returns the callable to time (it is jit-compiled
    here); `candidates` are dicts of KernelConfig block overrides.  A
    candidate the compiler refuses (a tile the backend cannot lay out, more
    VMEM than it may use) is skipped and named in the winner's `refused`
    entry; if every candidate is refused, the last refusal is raised.  The
    winner (augmented with its measured `us`) is cached under `key`.  The
    search runs under an `autotune` span naming `kernel`."""
    cands = list(candidates)
    if not cands:
        return {}
    cached = _TUNE.get(key)
    if cached is not None:
        return cached
    if len(cands) == 1:
        choice = dict(cands[0])
        _TUNE.put(key, choice)
        return choice
    best, best_t = None, float("inf")
    refused: list[str] = []
    with span("autotune", kernel=kernel, candidates=len(cands)):
        for cand in cands:
            try:
                compiled = jax.jit(build(cand)).lower(*args).compile()
            except Exception as exc:  # noqa: BLE001 - any compiler refusal
                if len(refused) == len(cands) - 1:
                    raise RuntimeError(f"autotune: the compiler refused all "
                                       f"{len(cands)} candidates") from exc
                why = (str(exc).strip().splitlines()
                       or [type(exc).__name__])[0]
                label = ",".join(f"{k}={v}" for k, v in sorted(cand.items()))
                refused.append(f"{label}: {why[:80]}")
                continue
            t = time_fn(compiled, args, iters)
            if t < best_t:
                best, best_t = cand, t
    choice = dict(best)
    choice["us"] = best_t * 1e6
    if refused:
        choice["refused"] = tuple(refused)
    _TUNE.put(key, choice)
    return choice
