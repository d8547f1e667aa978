"""Serving engines over the dataflow pipeline.

Two engine generations live here:

  * `ServingEngine` -- the legacy CONTIGUOUS engine: one (B, max_len) cache,
    one shared position clock, teacher-forcing one prompt token per tick.
    Kept as the differential baseline and for the mesh-distributed decode
    path (KV sharding per distributed/sharding.py).

  * `PagedServingEngine` -- the production engine: the KV cache is a pool of
    fixed-size pages (block_pool.py) indexed through per-slot block tables,
    positions are a per-slot (B,) clock threaded down to the decode-attention
    kernels (each slot attends exactly its own [0, valid) range -- a refilled
    slot can never see the previous occupant's stale entries), prompts
    prefill in chunks mixed into decode ticks (scheduler.py), and finished
    prompts publish their blocks to a prefix cache (prefix_cache.py).
    Capacity comes from an on-device profiling pass (`PagedKVExecutor`, the
    vLLM ExecutorBase shape: get_max_allowed_kv_blocks -> initialize_cache).

  * `AsyncServingEngine` wraps the paged engine in a background tick loop:
    `submit()` returns a streaming `RequestHandle` immediately; `drain()`
    stops the loop after in-flight work completes.

Every tick -- paged or legacy -- is ONE compiled program over the full slot
batch, served from the process-wide executable cache, or traced through the
dataflow pipeline when `ServeConfig.compile_mode` selects an executor
backend ("kitsune" runs the decode tick on prebound ExecutionPlans).
"""
from __future__ import annotations

import functools
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.compiler import cached_jit
from repro.core.costmodel import paged_decode_traffic
from repro.core.executor import executable_cache
from repro.distributed.sharding import NULL
from repro.kernels import KernelConfig
from repro.models import get_model

from .block_pool import BlockPool, OutOfBlocks
from .faults import DeadlineExceeded, EngineError, FaultInjector, QueueFull
from .prefix_cache import PrefixCache
from .scheduler import Request, Scheduler, blocks_for


@dataclass(frozen=True)
class ServeConfig:
    max_len: int
    batch: int
    greedy: bool = True
    temperature: float = 1.0
    # None: decode tick is one cached_jit program (production default).
    # "bsp" | "vertical" | "kitsune": the tick is TRACED through the
    # compiler's capture front-end (core/trace.py) and served from the
    # chosen executor backend -- the decode loop goes through the same
    # dataflow pipeline as every other workload.
    compile_mode: str | None = None
    # Optional LRU bound for the PROCESS-WIDE executable cache.  Engines of
    # many shapes/configs share one cache; long-lived serving processes can
    # cap it here (evicted shapes re-lower on next use; eviction counts are
    # in executable_cache().stats()).  None (default) leaves whatever bound
    # is already in force untouched -- the knob is global and
    # last-setter-wins, so set it from ONE place in a deployment.  Note the
    # cap bounds the cache's OWN refs; live ExecutionPlans keep their bound
    # executables until the per-engine plan LRU (Engine.MAX_PLANS) or the
    # engine itself drops them.
    cache_capacity: int | None = None
    # -- paged engine knobs -------------------------------------------------
    block_size: int = 8            # token positions per KV page
    prefill_chunk: int = 8         # max prompt tokens one slot feeds per tick
    token_budget: int | None = None  # tokens per tick across the batch
    num_blocks: int | None = None    # pool size; None -> profiling pass
    mem_budget_bytes: int | None = None  # profiling budget when no device stats
    prefix_caching: bool = True
    # False (default) pins the per-tick KV view at max_blocks: every tick
    # reduces over the same attention length, which keeps outputs BITWISE
    # independent of what the other slots are doing (XLA regroups reduction
    # trees per length, so varying view lengths are value-equal but can flip
    # a near-tie argmax).  True sizes the view at the ACTIVE-SLOT max (the
    # longest live slot's block count, no padding tax): less gather and
    # attention work per tick, more compiled programs (<= max_blocks per
    # chunk width), and only value-level (not bitwise) batch invariance.
    view_buckets: bool = False
    # Tick KV data path (docs/SERVING.md "Tick data path").  "native"
    # (default): attention reads/writes the flat page pools through the
    # block tables directly -- no pool->view gather, no trailing scatter.
    # "gather": the PR-5 path (gather the dense view, flash-decode it,
    # scatter written columns back), kept as the differential oracle; the
    # two modes are bitwise-equal (tests/test_paged_attention.py).
    paged_attention: str = "native"
    max_new_tokens: int | None = None    # default per-request cap
    # -- fault tolerance (docs/SERVING.md "Failure model") -----------------
    # Scripted fault schedule (tuple of faults.FaultSpec) + RNG seed: tests
    # and the chaos bench install deterministic failures at named sites.
    fault_plan: tuple = ()
    fault_seed: int = 0
    # Opt-in guard: after each tick, decode logits of sampling slots are
    # checked for NaN/Inf; a poisoned slot fails with EngineError(site=
    # "tick.logits") and releases its blocks instead of streaming garbage.
    nan_guard: bool = False
    # Consecutive failed ticks before the engine gives up isolating blame
    # and transitions to the terminal "degraded" state (health()).
    max_tick_retries: int = 3
    # Consecutive transient prefill-chunk failures tolerated per request
    # before its handle is failed.
    max_chunk_retries: int = 3
    # Waiting-queue bound: submit() raises QueueFull past it (async submit
    # can block-with-timeout instead).  None = unbounded.
    max_queue: int | None = None
    # Default per-request deadline in seconds (submit(deadline_s=) wins).
    default_deadline_s: float | None = None


def _apply_cache_capacity(sc: ServeConfig) -> None:
    """Apply ServeConfig.cache_capacity to the process-wide executable cache,
    warning when it would SHRINK a larger capacity some other engine set --
    the knob is global, and silently evicting a co-tenant's executables is
    exactly the kind of action that should be loud."""
    if sc.cache_capacity is None:
        return
    cache = executable_cache()
    cur = cache.stats()["capacity"]
    if cur is not None and sc.cache_capacity < cur:
        warnings.warn(
            f"ServeConfig.cache_capacity={sc.cache_capacity} shrinks the "
            f"process-wide executable cache from capacity {cur}; other "
            "engines in this process share that cache and may re-lower "
            "evicted shapes", stacklevel=3)
    cache.set_capacity(sc.cache_capacity)


def serve_step(params, state, cfg: ArchConfig, *,
               kernels: KernelConfig = KernelConfig(), sharder=NULL):
    """One decode tick for the whole batch (legacy contiguous engine).

    state = {"tokens": (B,), "pos": scalar, "cache": {...}}
    Returns new state with sampled next tokens and the updated cache.
    """
    model = get_model(cfg)
    logits, cache = model.decode_step(params, state["tokens"], state["pos"],
                                      state["cache"], kernels=kernels,
                                      sharder=sharder)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return {"tokens": nxt, "pos": state["pos"] + 1, "cache": cache,
            "logits": logits}


class ServingEngine:
    """Legacy host-side request manager: continuous batching over fixed
    slots with ONE contiguous (B, max_len) cache and a shared position clock.

    Kept as the paged engine's differential baseline.  Its known limitation
    -- a slot refilled mid-stream attends the previous occupant's stale
    cache entries because all slots share one position -- is exactly what
    `PagedServingEngine`'s per-slot valid-range tracking fixes."""

    def __init__(self, cfg: ArchConfig, params, sc: ServeConfig, *,
                 kernels: KernelConfig = KernelConfig(), sharder=NULL,
                 eos_id: int = 1):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.model = get_model(cfg)
        self.kernels = kernels
        self.sharder = sharder
        self.eos = eos_id
        self.queue: deque[tuple[int, list[int]]] = deque()  # (request_id, prompt)
        self.slots: list[dict | None] = [None] * sc.batch
        self.done: dict[int, list[int]] = {}
        self.cache = self.model.init_cache(sc.batch, sc.max_len)
        self.tokens = jnp.zeros((sc.batch,), jnp.int32)
        self.pos = jnp.zeros((), jnp.int32)
        _apply_cache_capacity(sc)
        # Decode tick through the compiler's executable cache: the first
        # tick per (batch, cache shape) lowers+compiles; every later tick --
        # and every later engine with the same config -- reuses the cached
        # executable instead of re-jitting (repro.compile()'s hot-path
        # contract applied to the serving loop).
        step_fn = functools.partial(serve_step, cfg=cfg, kernels=kernels,
                                    sharder=sharder)
        if sc.compile_mode is not None:
            # dataflow-pipeline path: trace the tick into an operator graph
            # and run it on the selected executor backend.  Repeated ticks
            # hit the same executable cache (zero relowerings).
            import repro
            example_state = {"tokens": self.tokens, "pos": self.pos,
                             "cache": self.cache}
            self._step = repro.compile(step_fn, (params, example_state),
                                       mode=sc.compile_mode)
        else:
            self._step = cached_jit(
                step_fn,
                key=("serve_step", cfg.name, sc.batch, sc.max_len,
                     repr(kernels), str(getattr(sharder, "mesh", "null"))))

    # -- request lifecycle -------------------------------------------------
    def submit(self, request_id: int, prompt: list[int]):
        self.queue.append((request_id, prompt))

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot is None and self.queue:
                rid, prompt = self.queue.popleft()
                self.slots[i] = {"id": rid, "prompt": prompt, "out": [],
                                 "fed": 0}

    def tick(self) -> int:
        """One engine tick: feed prompt tokens or decode; returns #active."""
        self._admit()
        feed = np.array(self.tokens)   # writable host copy
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if slot["fed"] < len(slot["prompt"]):
                feed[i] = slot["prompt"][slot["fed"]]   # teacher-force prompt
                slot["fed"] += 1
        state = {"tokens": jnp.asarray(feed), "pos": self.pos,
                 "cache": self.cache}
        out = self._step(self.params, state)
        self.cache = out["cache"]
        self.pos = out["pos"]
        nxt = np.asarray(out["tokens"])
        active = 0
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if slot["fed"] >= len(slot["prompt"]):
                slot["out"].append(int(nxt[i]))
            limit = self.sc.max_len - len(slot["prompt"]) - 1
            if (slot["out"] and slot["out"][-1] == self.eos) or \
                    len(slot["out"]) >= limit:
                self.done[slot["id"]] = slot["out"]
                self.slots[i] = None
            else:
                active += 1
        self.tokens = jnp.asarray(nxt)
        return active + len(self.queue)

    def run_until_done(self, max_ticks: int = 10_000) -> dict[int, list[int]]:
        for _ in range(max_ticks):
            if self.tick() == 0:
                break
        return self.done


# ---------------------------------------------------------------------------
# paged engine
# ---------------------------------------------------------------------------

class RequestHandle:
    """Future/stream for one submitted request.

    `tokens()` snapshots what has been generated so far (streaming);
    `result()` blocks until completion and returns the full output, raising
    if the request was rejected or failed."""

    def __init__(self, rid: int, prompt: list[int]):
        self.rid = rid
        self.prompt = prompt
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._tokens: list[int] = []
        self._error: BaseException | None = None

    def _append(self, tok: int) -> None:
        with self._lock:
            self._tokens.append(tok)

    def _finish(self) -> None:
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def error(self) -> BaseException | None:
        return self._error

    def tokens(self) -> list[int]:
        with self._lock:
            return list(self._tokens)

    def result(self, timeout: float | None = None) -> list[int]:
        if not self._event.wait(timeout):
            # a handle failed while we were waiting still reports ITS error
            # (the stored EngineError beats the caller's timeout), and the
            # timeout itself says who stalled and how far it got
            if self._error is not None:
                raise self._error
            raise TimeoutError(
                f"request {self.rid} still running after {timeout}s "
                f"({len(self.tokens())} tokens so far)")
        if self._error is not None:
            raise self._error
        return self.tokens()


# batch axis of each recurrent (non-KV) cache entry, per models/lm.init_cache
_AUX_BATCH_AXIS = {"ssm": 1, "mC": 2, "mn": 2, "mm": 2,
                   "sc": 2, "sn": 2, "sm": 2}


def paged_tick(params, state, cfg: ArchConfig, *,
               kernels: KernelConfig = KernelConfig(), sharder=NULL,
               block_size: int, n_steps: int, mode: str = "gather"):
    """One unified serving tick over paged KV: run `n_steps` decode steps
    with per-slot activity masks (chunked prefill and decode mixed in one
    program).  Two KV data paths (docs/SERVING.md "Tick data path"):

    mode="gather" (the PR-5 oracle): gather a dense per-slot view from the
    page pool, decode against the view, scatter the newly written positions
    back to their pages -- a full O(view) pool copy per tick.
    mode="native": attention indexes the pools through the block tables
    directly (models decode in paged mode); new K/V land on their page rows
    as they are produced, so the view materialization AND the trailing
    scatter disappear.  Bitwise-equal to "gather": both paths run the same
    grouped decode math over bit-identically gathered rows of the same
    view length (tests/test_paged_attention.py).

    state:
      tokens (B, n_steps) int32  input token per slot per step (padded)
      n_tok  (B,) int32          active steps per slot; 0 = idle slot
      pos    (B,) int32          per-slot write position at tick start
      tables (B, V) int32        physical page id per logical block
      kp/vp  (P, G, A, Hkv, D)   flat page pools (P = (num_blocks+1) * bs;
                                 row block 0 is the reserved null page)
      + recurrent entries (ssm/mC/...) keyed as in models init_cache

    Bitwise contract: a slot's outputs depend only on ITS OWN fed tokens.
    Masked-out steps write at a stationary position that a later active step
    either overwrites or that is redirected to the null page (native) /
    skipped by the scatter (gather); view positions beyond a slot's valid
    length score exp(-1e30 - m) == 0.0 exactly in f32, so neither other
    slots' activity nor the view padding perturbs a single bit.
    """
    model = get_model(cfg)
    tokens, n_tok, pos = state["tokens"], state["n_tok"], state["pos"]
    b = tokens.shape[0]
    bs = block_size
    has_kv = "kp" in state
    native = has_kv and mode == "native"
    cache: dict[str, Any] = {}
    if native:
        kp, vp, tables = state["kp"], state["vp"], state["tables"]
        v_blocks = tables.shape[1]
        cache["kp"], cache["vp"] = kp, vp
    elif has_kv:
        kp, vp, tables = state["kp"], state["vp"], state["tables"]
        v_blocks = tables.shape[1]
        view_len = v_blocks * bs
        # logical view rows -> flat page rows: block id * bs + offset
        rows = (tables[:, :, None] * bs
                + jnp.arange(bs, dtype=tables.dtype)[None, None, :]
                ).reshape(b, view_len)
        # (B, L, G, A, H, D) -> (G, A, B, H, L, D): the layout decode expects
        cache["k"] = kp[rows].transpose(2, 3, 0, 4, 1, 5)
        cache["v"] = vp[rows].transpose(2, 3, 0, 4, 1, 5)
    for name in _AUX_BATCH_AXIS:
        if name in state:
            cache[name] = state[name]

    pos0 = pos
    logits = None
    for j in range(n_steps):
        active = j < n_tok
        if native:
            # flat pool row for each slot's new K/V; inactive slots redirect
            # to the null page row 0 (same semantics as the gather path's
            # scatter skipping invalid columns)
            blk = jnp.minimum(pos // bs, v_blocks - 1)
            phys_w = jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0]
            write_rows = jnp.where(active, phys_w * bs + pos % bs, 0)
            lg, new = model.decode_step(params, tokens[:, j], pos, cache,
                                        kernels=kernels, sharder=sharder,
                                        block_tables=tables, block_size=bs,
                                        kv_write_rows=write_rows)
            cache["kp"], cache["vp"] = new["kp"], new["vp"]
        else:
            lg, new = model.decode_step(params, tokens[:, j], pos, cache,
                                        kernels=kernels, sharder=sharder)
            if has_kv:
                # inactive slots wrote garbage at their stationary pos:
                # either a later active step overwrites it or the scatter
                # below skips it
                cache["k"], cache["v"] = new["k"], new["v"]
        for name, ax in _AUX_BATCH_AXIS.items():
            if name in cache:
                shp = [1] * cache[name].ndim
                shp[ax] = b
                cache[name] = jnp.where(active.reshape(shp), new[name],
                                        cache[name])
        logits = lg if logits is None else jnp.where(active[:, None], lg,
                                                     logits)
        pos = jnp.where(active, pos + 1, pos)

    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = {"tokens_next": nxt, "logits": logits, "pos": pos}
    if native:
        # K/V already live on their page rows -- no trailing scatter
        out["kp"], out["vp"] = cache["kp"], cache["vp"]
    elif has_kv:
        # scatter the C freshly written view columns back to their pages;
        # invalid (beyond n_tok) columns redirect to the null page row 0
        steps = jnp.arange(n_steps, dtype=pos0.dtype)
        wpos = pos0[:, None] + steps[None, :]                  # (B, C)
        wvalid = steps[None, :] < n_tok[:, None]
        phys = jnp.take_along_axis(
            tables, jnp.minimum(wpos // bs, v_blocks - 1), axis=1)
        flat = jnp.where(wvalid, phys * bs + wpos % bs, 0).reshape(-1)
        cols = jnp.minimum(wpos, view_len - 1)[None, None, :, None, :, None]
        kc = jnp.take_along_axis(cache["k"], cols, axis=4)     # (G,A,B,H,C,D)
        vc = jnp.take_along_axis(cache["v"], cols, axis=4)
        kc = kc.transpose(2, 4, 0, 1, 3, 5).reshape(b * n_steps, *kp.shape[1:])
        vc = vc.transpose(2, 4, 0, 1, 3, 5).reshape(b * n_steps, *vp.shape[1:])
        out["kp"] = kp.at[flat].set(kc.astype(kp.dtype))
        out["vp"] = vp.at[flat].set(vc.astype(vp.dtype))
    for name in _AUX_BATCH_AXIS:
        if name in cache:
            out[name] = cache[name]
    return out


class PagedKVExecutor:
    """Capacity owner for the paged engine, in the vLLM ExecutorBase shape:
    `get_max_allowed_kv_blocks()` runs a profiling pass (parameter bytes +
    compiled-tick working set against the device budget), the engine decides
    the final count, `initialize_cache(n)` materializes the page pools."""

    DEFAULT_BUDGET = 256 * 1024 * 1024   # no device stats (CPU): 256 MiB
    # share of the device's bytes_limit the engine plans for (vLLM's
    # gpu_memory_utilization default): the rest is left to the allocator
    # and the loaded executables, which the tick's memory_analysis omits
    DEVICE_UTILIZATION = 0.9

    def __init__(self, cfg: ArchConfig, params, sc: ServeConfig, *,
                 kernels: KernelConfig = KernelConfig(), sharder=NULL,
                 fault: FaultInjector | None = None):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.kernels = kernels
        self.sharder = sharder
        self.fault = fault
        self.profile_error: str | None = None
        template = get_model(cfg).init_cache(1, sc.block_size)
        if "k" not in template:
            raise ValueError(f"{cfg.name}: no KV cache to page")
        g, a, _, h, _, d = template["k"].shape
        self.page_shape = (g, a, h, d)
        self.kv_dtype = template["k"].dtype
        self.max_blocks = blocks_for(sc.max_len, sc.block_size)
        # bytes of ONE logical block: its K page + its V page
        self.block_bytes = 2 * sc.block_size * g * a * h * d \
            * jnp.dtype(self.kv_dtype).itemsize

    def _device_budget(self) -> int:
        if self.sc.mem_budget_bytes is not None:
            return self.sc.mem_budget_bytes
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            return self.DEFAULT_BUDGET
        return int(limit * self.DEVICE_UTILIZATION)

    def profile_run(self) -> int:
        """Working-set bytes of the widest compiled tick the engine runs (a
        full prefill chunk over the max_blocks view, probe-sized pool): its
        temporaries plus the outputs that do not alias the donated state --
        the activation term of the capacity model.  Raises MemoryError at
        the `executor.profile` fault site; a real compile failure propagates
        (a tick the backend refuses must stop engine construction, not
        shrink the capacity model)."""
        if self.fault is not None and self.fault.check("executor.profile"):
            raise MemoryError("injected OOM at executor.profile")
        sc = self.sc
        n_steps = max(1, sc.prefill_chunk)
        probe = functools.partial(paged_tick, cfg=self.cfg,
                                  kernels=self.kernels, sharder=self.sharder,
                                  block_size=sc.block_size, n_steps=n_steps,
                                  mode=sc.paged_attention)
        state = self._abstract_state(n_steps=n_steps,
                                     v_blocks=self.max_blocks, num_blocks=1)
        p_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
            self.params)
        compiled = jax.jit(probe, donate_argnums=(1,)).lower(
            p_abs, state).compile()
        mem = compiled.memory_analysis()
        return int(mem.temp_size_in_bytes + mem.output_size_in_bytes
                   - mem.alias_size_in_bytes)

    def _abstract_state(self, *, n_steps: int, v_blocks: int,
                        num_blocks: int) -> dict:
        sc = self.sc
        b = sc.batch
        g, a, h, d = self.page_shape
        pool_rows = (num_blocks + 1) * sc.block_size
        st = {"tokens": jax.ShapeDtypeStruct((b, n_steps), jnp.int32),
              "n_tok": jax.ShapeDtypeStruct((b,), jnp.int32),
              "pos": jax.ShapeDtypeStruct((b,), jnp.int32),
              "tables": jax.ShapeDtypeStruct((b, v_blocks), jnp.int32),
              "kp": jax.ShapeDtypeStruct((pool_rows, g, a, h, d),
                                         self.kv_dtype),
              "vp": jax.ShapeDtypeStruct((pool_rows, g, a, h, d),
                                         self.kv_dtype)}
        aux = get_model(self.cfg).init_cache(b, 1)
        for name in _AUX_BATCH_AXIS:
            if name in aux:
                st[name] = jax.ShapeDtypeStruct(aux[name].shape,
                                                aux[name].dtype)
        return st

    def get_max_allowed_kv_blocks(self) -> tuple[int, int]:
        """(device_blocks, swap_blocks).  device_blocks = what fits in the
        budget after parameters and the tick working set; floored at
        max_blocks + batch so a full-length request plus one block per slot
        always fits.  No host swap tier here, so swap_blocks is 0."""
        budget = self._device_budget()
        param_bytes = sum(int(np.prod(jnp.shape(x)))
                          * jnp.asarray(x).dtype.itemsize
                          for x in jax.tree_util.tree_leaves(self.params))
        floor = self.max_blocks + self.sc.batch
        try:
            act_bytes = self.profile_run()
        except MemoryError as exc:
            # profiling OOMed: fall back to the guaranteed-viable floor
            # capacity instead of killing engine construction -- the engine
            # runs degraded-capacity but correct, and reports why
            self.profile_error = str(exc)
            return floor, 0
        n = (budget - param_bytes - act_bytes) // self.block_bytes
        return max(int(n), floor), 0

    def initialize_cache(self, num_blocks: int) -> tuple[jax.Array, jax.Array]:
        """Materialize the K and V page pools: row block 0 is the reserved
        null page, usable pages are rows [bs, (num_blocks+1)*bs)."""
        g, a, h, d = self.page_shape
        rows = (num_blocks + 1) * self.sc.block_size
        kp = jnp.zeros((rows, g, a, h, d), self.kv_dtype)
        return kp, jnp.zeros_like(kp)


class PagedServingEngine:
    """Block-paged continuous batching with per-slot position tracking.

    Each slot carries its own (pos, block-table row); the decode kernels see
    a per-slot (B,) valid-length vector, so a slot refilled mid-stream is
    bitwise-identical to serving its request alone.  Prompts prefill in
    budget-bounded chunks mixed into decode ticks; finished prompts publish
    their KV pages to the prefix cache for later requests to reuse."""

    def __init__(self, cfg: ArchConfig, params, sc: ServeConfig, *,
                 kernels: KernelConfig = KernelConfig(), sharder=NULL,
                 eos_id: int = 1, clock=time.monotonic):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.model = get_model(cfg)
        self.kernels = kernels
        self.sharder = sharder
        self.eos = eos_id
        self.clock = clock               # injectable for deadline tests
        if cfg.family == "encdec":
            raise ValueError("paged serving covers decoder-only families")
        if sc.paged_attention not in ("gather", "native"):
            raise ValueError("paged_attention must be 'gather' or 'native', "
                             f"got {sc.paged_attention!r}")
        _apply_cache_capacity(sc)
        self.injector = (FaultInjector(tuple(sc.fault_plan), sc.fault_seed)
                         if sc.fault_plan else None)

        b = sc.batch
        full = self.model.init_cache(b, 1)
        self.aux_init = {k: v for k, v in full.items() if k not in ("k", "v")}
        self.aux = dict(self.aux_init)
        self.has_kv = "k" in full
        self.max_blocks = blocks_for(sc.max_len, sc.block_size)
        if self.has_kv:
            self.executor = PagedKVExecutor(cfg, params, sc, kernels=kernels,
                                            sharder=sharder,
                                            fault=self.injector)
            if sc.num_blocks is not None:
                num = sc.num_blocks
            else:
                num, _ = self.executor.get_max_allowed_kv_blocks()
            self.kp, self.vp = self.executor.initialize_cache(num)
            self.pool = BlockPool(
                num, sc.block_size,
                on_evict=lambda key, bid: self.prefix.on_evict(key, bid),
                fault=self.injector)
            self.prefix = PrefixCache(self.pool)
            self.tables = np.zeros((b, self.max_blocks), np.int32)
        else:
            self.executor = None
            self.pool = None
            self.prefix = None
            self.tables = None
        # prefix reuse is only sound when KV pages are the WHOLE model state:
        # recurrent families would need the matching ssm/lstm state too
        self.prefix_enabled = (sc.prefix_caching and self.has_kv
                               and not self.aux_init)

        self.scheduler = Scheduler(block_size=sc.block_size,
                                   prefill_chunk=sc.prefill_chunk,
                                   token_budget=sc.token_budget,
                                   n_slots=b, max_queue=sc.max_queue)
        self.slots: list[dict | None] = [None] * b
        self.pos = np.zeros(b, np.int64)
        self.done: dict[int, list[int]] = {}
        self.failed: dict[int, EngineError] = {}
        self.handles: dict[int, RequestHandle] = {}
        self._rid = 0
        self._steps: dict[tuple[int, int], Any] = {}
        self._view_buckets = self._make_view_buckets()
        self.ticks = 0
        self.last_logits = None
        self.tokens_out = 0
        self.peak_active = 0
        # analytic per-tick KV bytes for BOTH tick data paths, accumulated
        # from each tick's actual geometry (costmodel.paged_decode_traffic)
        # -- the bench's bytes-moved table reads these off stats()
        self.kv_traffic = {"ticks": 0, "gather_bytes": 0, "native_bytes": 0}
        # -- health/degraded-mode state (health()) -------------------------
        self.state = "healthy"           # healthy | degraded | stopped
        self.last_error: EngineError | None = None
        self.consecutive_failures = 0
        self.ticks_since_progress = 0
        self._culprit_rid: int | None = None   # tick-scoped blame context
        self._tick_admitted: list[int] = []
        self._tick_no = 0
        self._progressed = False

    # -- geometry ----------------------------------------------------------
    def _make_view_buckets(self) -> list[int]:
        if not self.has_kv:
            return [0]
        if not self.sc.view_buckets:
            return [self.max_blocks]
        # exact active-max sizing: the view is as long as the longest active
        # slot needs, nothing more (was pow2 buckets -- up to 2x padding).
        # At most max_blocks compiled tick programs per chunk width, and the
        # same bitwise trade as before: view length now varies with the
        # batch mix, so outputs are value-equal but not bitwise
        # batch-invariant (docs/SERVING.md "Tick data path").
        return list(range(1, self.max_blocks + 1))

    def _view_for(self, need_blocks: int) -> int:
        for v in self._view_buckets:
            if v >= need_blocks:
                return v
        return self._view_buckets[-1]

    # -- compiled tick per (chunk width, view) bucket ----------------------
    def _get_step(self, n_steps: int, v_blocks: int):
        key = (n_steps, v_blocks)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        sc = self.sc
        base = functools.partial(paged_tick, cfg=self.cfg,
                                 kernels=self.kernels, sharder=self.sharder,
                                 block_size=sc.block_size, n_steps=n_steps,
                                 mode=sc.paged_attention)
        # The tick state (kp/vp pools, aux, per-tick tokens/pos/tables) is
        # dead after every call -- the engine rebinds all of it from the
        # step's outputs -- so donate it: XLA aliases the KV pools and the
        # scatter-back updates pages IN PLACE instead of copying the whole
        # pool each tick (the pool can be most of device memory).
        if sc.compile_mode is not None:
            import repro
            example = self._example_state(n_steps, v_blocks)
            fn = repro.compile(base, (self.params, example),
                               mode=sc.compile_mode, donate_argnums=(1,))
        else:
            num = self.pool.num_blocks if self.pool else 0
            fn = cached_jit(
                base,
                key=("paged_tick", self.cfg.name, sc.batch, sc.block_size,
                     n_steps, v_blocks, num, sc.paged_attention,
                     repr(self.kernels),
                     str(getattr(self.sharder, "mesh", "null"))),
                donate_argnums=(1,))
        self._steps[key] = fn
        return fn

    def _example_state(self, n_steps: int, v_blocks: int) -> dict:
        b = self.sc.batch
        st = {"tokens": jnp.zeros((b, n_steps), jnp.int32),
              "n_tok": jnp.zeros((b,), jnp.int32),
              "pos": jnp.zeros((b,), jnp.int32)}
        if self.has_kv:
            st["tables"] = jnp.zeros((b, v_blocks), jnp.int32)
            st["kp"], st["vp"] = self.kp, self.vp
        st.update(self.aux)
        return st

    # -- request lifecycle -------------------------------------------------
    def submit(self, prompt: list[int], rid: int | None = None,
               max_new_tokens: int | None = None,
               deadline_s: float | None = None) -> RequestHandle:
        """Enqueue a request.  Raises QueueFull when the bounded admission
        queue (`ServeConfig.max_queue`) is at capacity -- explicit
        backpressure the caller must absorb (AsyncServingEngine.submit can
        block-with-timeout instead).  `deadline_s` (or the config default)
        fails the request with DeadlineExceeded once that many seconds pass
        -- queued requests before any prefill budget is spent, in-flight
        requests by slot eviction at the next tick."""
        if self.state != "healthy":
            if rid is None:
                self._rid += 1
                rid = self._rid
            handle = RequestHandle(rid, list(prompt))
            self.handles[rid] = handle
            handle._fail(EngineError(
                f"engine is {self.state}: request {rid} rejected",
                site="engine." + self.state, tick=self.ticks, rid=rid))
            return handle
        if deadline_s is None:
            deadline_s = self.sc.default_deadline_s
        if rid is None:
            self._rid += 1
            rid = self._rid
        handle = RequestHandle(rid, list(prompt))
        req = Request(rid=rid, prompt=list(prompt), handle=handle,
                      max_new=max_new_tokens or self.sc.max_new_tokens,
                      deadline=(None if deadline_s is None
                                else self.clock() + deadline_s))
        if len(prompt) >= self.sc.max_len:
            self.handles[rid] = handle
            self.scheduler.rejected += 1
            handle._fail(ValueError(
                f"prompt of {len(prompt)} tokens >= max_len {self.sc.max_len}"))
            return handle
        if self.pool is not None and \
                self.scheduler.admission_cost(req) > self.pool.num_blocks:
            self.handles[rid] = handle
            self.scheduler.rejected += 1
            handle._fail(ValueError(
                f"request needs {self.scheduler.admission_cost(req)} blocks; "
                f"pool holds {self.pool.num_blocks}"))
            return handle
        if not self.scheduler.submit(req):
            # bounded queue full: backpressure is an EXCEPTION, not a failed
            # handle -- the caller must know to retry/shed, and no handle
            # leaks into self.handles
            raise QueueFull(
                f"admission queue full ({self.sc.max_queue} waiting); "
                f"request {rid} not enqueued",
                site="engine.queue", tick=self.ticks, rid=rid)
        self.handles[rid] = handle
        return handle

    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        resets = []
        for i in free:
            req = self.scheduler.next_admission(self.pool)
            if req is None:
                break
            reused_bids: list[int] = []
            reused = 0
            if self.prefix_enabled and not req.resume_out:
                reused_bids, reused = self.prefix.match(req.prompt)
            if self.tables is not None:
                self.tables[i, :] = 0
                self.tables[i, :len(reused_bids)] = reused_bids
            self.slots[i] = {
                "rid": req.rid, "req": req, "prompt": req.prompt,
                "seq": req.feed, "out": list(req.resume_out),
                "fed": reused, "nblocks": len(reused_bids), "last": None,
                "handle": req.handle, "max_new": req.max_new,
                "admit_seq": self.scheduler.admit_seq,
                "chunk_fails": 0,
            }
            self.pos[i] = reused
            self._tick_admitted.append(req.rid)
            resets.append(i)
        if resets and self.aux_init:
            # reinitialize recurrent state for refilled slots only
            mask = np.zeros(self.sc.batch, bool)
            mask[resets] = True
            m = jnp.asarray(mask)
            for name, init in self.aux_init.items():
                ax = _AUX_BATCH_AXIS[name]
                shp = [1] * init.ndim
                shp[ax] = self.sc.batch
                self.aux[name] = jnp.where(m.reshape(shp), init,
                                           self.aux[name])

    def _release(self, i: int, *, cache_prefix: bool) -> None:
        slot = self.slots[i]
        if self.pool is not None:
            bids = [int(b) for b in self.tables[i, :slot["nblocks"]]]
            if cache_prefix and self.prefix_enabled:
                self.prefix.insert(slot["prompt"], bids)
            for bid in bids:
                self.pool.decref(bid)
            self.tables[i, :] = 0
        self.pos[i] = 0
        self.slots[i] = None

    def _preempt(self, i: int) -> None:
        """Preemption-by-recompute: tear the slot down, requeue its request
        (prompt + generated-so-far) at the queue head.  Greedy decoding
        makes the recompute bitwise-exact, so the handle keeps streaming."""
        slot = self.slots[i]
        req = slot["req"]
        req.resume_out = list(slot["out"])
        self._release(i, cache_prefix=False)
        if self.pool is not None and \
                self.scheduler.admission_cost(req) > self.pool.num_blocks:
            self.scheduler.rejected += 1
            req.handle._fail(OutOfBlocks(
                f"request {req.rid} grew past pool capacity"))
            return
        self.scheduler.requeue(req)

    def _ensure_blocks(self, n_tok: list[int]) -> None:
        """Allocate pages so every slot's table covers pos + n_tok this
        tick; on exhaustion, preempt the newest slot and retry (the slot
        being grown preempts ITSELF when it is the newest)."""
        if self.pool is None:
            return
        order = sorted((s["admit_seq"], i)
                       for i, s in enumerate(self.slots) if s is not None)
        for _, i in order:
            slot = self.slots[i]
            if slot is None or n_tok[i] == 0:
                continue
            # blame context: if allocation fails terminally, the request
            # whose growth triggered it is the culprit
            self._culprit_rid = slot["rid"]
            need = blocks_for(int(self.pos[i]) + n_tok[i], self.sc.block_size)
            while slot["nblocks"] < need:
                try:
                    bid = self.pool.alloc()
                except OutOfBlocks:
                    victim = self.scheduler.pick_victim(self.slots)
                    if victim is None:
                        raise
                    self._preempt(victim)
                    n_tok[victim] = 0
                    if victim == i:
                        break
                    continue
                self.tables[i, slot["nblocks"]] = bid
                slot["nblocks"] += 1
        self._culprit_rid = None

    # -- fault isolation ---------------------------------------------------
    def _slot_of(self, rid: int) -> int | None:
        for i, s in enumerate(self.slots):
            if s is not None and s["rid"] == rid:
                return i
        return None

    def _fail_request(self, rid: int, err: EngineError) -> None:
        """Terminal failure of ONE request: release its slot/blocks (or pull
        it from the waiting queue) and fail its handle -- co-tenants keep
        their state untouched, so survivors stay bitwise identical."""
        self.failed[rid] = err
        i = self._slot_of(rid)
        if i is not None:
            self.slots[i]["handle"]._fail(err)
            self._release(i, cache_prefix=False)
            return
        for req in list(self.scheduler.waiting):
            if req.rid == rid:
                self.scheduler.waiting.remove(req)
                req.handle._fail(err)
                return
        h = self.handles.get(rid)
        if h is not None and not h.done():
            h._fail(err)

    def _pick_culprit(self) -> int | None:
        """Blame for a whole-tick failure: the explicit culprit context if
        set (e.g. the slot whose growth exhausted the pool), else the
        request admitted THIS tick (its shape/chunk is what changed), else
        the newest admission among live slots."""
        if self._culprit_rid is not None:
            return self._culprit_rid
        for rid in reversed(self._tick_admitted):
            if self._slot_of(rid) is not None:
                return rid
        newest = None
        for s in self.slots:
            if s is not None and (newest is None
                                  or s["admit_seq"] > newest["admit_seq"]):
                newest = s
        return newest["rid"] if newest is not None else None

    def _enter_degraded(self, err: EngineError) -> None:
        """Terminal engine state: stop isolating, fail everything in flight
        and queued so every handle reaches a terminal state (drain()/
        result() raise instead of hanging), report via health()."""
        self.state = "degraded"
        self.last_error = err
        for i, s in enumerate(self.slots):
            if s is not None:
                tomb = EngineError(
                    f"engine degraded at tick {self._tick_no}: {err}",
                    site="engine.degraded", tick=self._tick_no, rid=s["rid"])
                self.failed[s["rid"]] = tomb
                s["handle"]._fail(tomb)
                self._release(i, cache_prefix=False)
        while self.scheduler.waiting:
            req = self.scheduler.waiting.popleft()
            tomb = EngineError(
                f"engine degraded at tick {self._tick_no}: {err}",
                site="engine.degraded", tick=self._tick_no, rid=req.rid)
            self.failed[req.rid] = tomb
            if req.handle is not None:
                req.handle._fail(tomb)

    def _fail_stragglers(self) -> None:
        """Late racers: submit() can check state == 'healthy' on the caller
        thread, lose the race with the tick thread's degraded transition,
        and append to the waiting queue AFTER _enter_degraded() drained it.
        Every non-healthy tick() sweeps such leftovers (queued requests and
        any live slots) into terminal failures so pending() reaches 0 and
        drain()/result() raise instead of hanging."""
        cause = self.last_error
        suffix = "" if cause is None else f": {cause}"
        for i, s in enumerate(self.slots):
            if s is not None:
                tomb = EngineError(
                    f"engine is {self.state}{suffix}",
                    site="engine." + self.state, tick=self.ticks,
                    rid=s["rid"])
                self.failed[s["rid"]] = tomb
                s["handle"]._fail(tomb)
                self._release(i, cache_prefix=False)
        while self.scheduler.waiting:
            req = self.scheduler.waiting.popleft()
            tomb = EngineError(
                f"engine is {self.state}{suffix}",
                site="engine." + self.state, tick=self.ticks, rid=req.rid)
            self.failed[req.rid] = tomb
            if req.handle is not None:
                req.handle._fail(tomb)

    def _expire_deadlines(self) -> None:
        now = self.clock()
        for req in self.scheduler.expire(now):
            err = DeadlineExceeded(
                f"request {req.rid} expired in queue "
                f"(deadline passed before admission)",
                site="engine.deadline", tick=self._tick_no, rid=req.rid)
            self.failed[req.rid] = err
            if req.handle is not None:
                req.handle._fail(err)
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            dl = s["req"].deadline
            if dl is not None and now > dl:
                self.scheduler.expired += 1
                self._fail_request(s["rid"], DeadlineExceeded(
                    f"request {s['rid']} expired in flight after "
                    f"{len(s['out'])} tokens", site="engine.deadline",
                    tick=self._tick_no, rid=s["rid"]))

    def health(self) -> dict:
        """Liveness snapshot: `state` is "healthy" until max_tick_retries
        CONSECUTIVE tick failures force the terminal "degraded" state
        ("stopped" once the owner closes the engine); plus the last
        structured error, the consecutive-failure count, and how many ticks
        have passed without any request making progress."""
        return {"state": self.state,
                "last_error": self.last_error,
                "consecutive_failures": self.consecutive_failures,
                "ticks_since_progress": self.ticks_since_progress,
                "ticks": self.ticks,
                "failed": len(self.failed)}

    # -- the tick ----------------------------------------------------------
    def tick(self) -> int:
        """One engine tick; returns #requests still in flight afterwards.

        Fault isolation: any exception inside the tick is caught, blamed on
        the culpable request (tick-scoped culprit context), and ONLY that
        handle fails with a structured EngineError -- the next tick runs
        without it.  After `ServeConfig.max_tick_retries` consecutive
        failing ticks the engine stops guessing and enters the terminal
        degraded state (health()) with every remaining handle failed."""
        if self.state != "healthy":
            self._fail_stragglers()
            return 0
        t = self.ticks                   # this attempt's tick number
        self.ticks = t + 1               # failed ticks advance the clock too
        self._tick_no = t
        self._tick_admitted = []
        self._culprit_rid = None
        self._progressed = False
        if self.injector is not None:
            self.injector.advance(t)
        self._expire_deadlines()
        try:
            left = self._tick_inner()
        except Exception as exc:  # noqa: BLE001 -- isolate, blame, keep serving
            self.consecutive_failures += 1
            self.ticks_since_progress += 1
            rid = self._pick_culprit()
            if isinstance(exc, EngineError):
                err = exc
            else:
                err = EngineError(
                    f"tick {t} failed at {type(exc).__name__}: {exc}",
                    site="tick.step", tick=t, rid=rid)
                err.__cause__ = exc
            err.tick, err.rid = t, rid
            self.last_error = err
            if rid is not None:
                self._fail_request(rid, err)
            if self.consecutive_failures >= self.sc.max_tick_retries or \
                    rid is None:
                self._enter_degraded(err)
            return self.pending()
        if self._progressed:
            self.consecutive_failures = 0
            self.ticks_since_progress = 0
        else:
            self.ticks_since_progress += 1
        return left

    def _prefill_faults(self, n_tok: list[int]) -> None:
        """`prefill.chunk` fault site: a firing spec makes one prefilling
        slot's chunk fail TRANSIENTLY -- the chunk is skipped this tick and
        retried on the next; after `max_chunk_retries` consecutive failures
        the request is failed for good."""
        if self.injector is None:
            return
        prefilling = [i for i, s in enumerate(self.slots)
                      if s is not None and n_tok[i] > 0
                      and s["fed"] < len(s["seq"])]
        if not prefilling:
            return
        spec = self.injector.check("prefill.chunk")
        if spec is None:
            for i in prefilling:
                self.slots[i]["chunk_fails"] = 0
            return
        victims = [i for i in prefilling
                   if spec.rid is None or self.slots[i]["rid"] == spec.rid]
        if not victims:
            return
        i = victims[-1]                     # newest-admitted qualifying slot
        slot = self.slots[i]
        n_tok[i] = 0
        slot["chunk_fails"] += 1
        if slot["chunk_fails"] > self.sc.max_chunk_retries:
            self._fail_request(slot["rid"], EngineError(
                f"request {slot['rid']}: prefill chunk failed "
                f"{slot['chunk_fails']} consecutive times",
                site="prefill.chunk", tick=self._tick_no, rid=slot["rid"]))

    def _tick_inner(self) -> int:
        self._admit()
        n_tok = self.scheduler.plan(self.slots)
        self._prefill_faults(n_tok)
        self._ensure_blocks(n_tok)
        active = [i for i, t in enumerate(n_tok) if t > 0]
        if not active:
            return sum(s is not None for s in self.slots) \
                + len(self.scheduler.waiting)
        self.peak_active = max(self.peak_active,
                               sum(s is not None for s in self.slots))
        if self.injector is not None:
            spec = self.injector.check("tick.step")
            if spec is not None:
                # fires BEFORE the compiled call so no tick state (and no
                # donated pool buffer) has been touched -- the retry without
                # the blamed request starts from a clean slate
                if spec.rid is not None:
                    self._culprit_rid = spec.rid
                raise EngineError(
                    f"injected fault at tick.step (tick {self._tick_no})",
                    site="tick.step", tick=self._tick_no, rid=spec.rid)

        c = 1 if max(n_tok) <= 1 else self.scheduler.chunk
        tokens = np.zeros((self.sc.batch, c), np.int32)
        for i in active:
            slot = self.slots[i]
            t = n_tok[i]
            if slot["fed"] < len(slot["seq"]):
                tokens[i, :t] = slot["seq"][slot["fed"]:slot["fed"] + t]
            else:
                tokens[i, 0] = slot["last"]
        state = {"tokens": jnp.asarray(tokens),
                 "n_tok": jnp.asarray(np.asarray(n_tok, np.int32)),
                 "pos": jnp.asarray(self.pos.astype(np.int32))}
        if self.has_kv:
            need = max(blocks_for(int(self.pos[i]) + n_tok[i],
                                  self.sc.block_size) for i in active)
            v_blocks = self._view_for(need)
            state["tables"] = jnp.asarray(self.tables[:, :v_blocks])
            state["kp"], state["vp"] = self.kp, self.vp
        else:
            v_blocks = 0
        state.update(self.aux)

        with warnings.catch_warnings():
            # donating the whole tick state is deliberate over-asking: the
            # small int32 feeds (tokens/pos/tables) can't alias because the
            # outputs they'd pair with differ in shape; only the kp/vp pool
            # aliasing matters, and jax's per-compile "not usable" warning
            # about the rest is expected noise
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out = self._get_step(c, v_blocks)(self.params, state)

        if self.has_kv:
            self.kp, self.vp = out["kp"], out["vp"]
        for name in self.aux:
            self.aux[name] = out[name]
        self.last_logits = out["logits"]     # (B, V) of this tick, on device
        nxt = np.asarray(out["tokens_next"]).copy()
        self.pos = np.asarray(out["pos"], np.int64).copy()
        self._progressed = True

        if self.has_kv:
            # analytic KV bytes for this tick's geometry, BOTH data paths
            # (the gather/native comparison in bench_serve reads stats())
            g_, a_, h_, d_ = self.executor.page_shape
            tr = paged_decode_traffic(
                batch=self.sc.batch, v_blocks=v_blocks,
                block_size=self.sc.block_size, n_steps=c,
                row_bytes=h_ * d_ * jnp.dtype(self.executor.kv_dtype).itemsize,
                n_sites=g_ * a_,
                alloc_blocks=int(np.count_nonzero(self.tables[:, :v_blocks])))
            self.kv_traffic["ticks"] += 1
            self.kv_traffic["gather_bytes"] += tr["gather_bytes"]
            self.kv_traffic["native_bytes"] += tr["native_bytes"]

        # slots that finish prefill this tick sample their first/next token
        sampling = [i for i in active
                    if self.slots[i]["fed"] + n_tok[i]
                    >= len(self.slots[i]["seq"])]
        logits_np = None
        if self.injector is not None and sampling:
            spec = self.injector.check("tick.logits")
            if spec is not None:
                # `tick.logits` fault site: corrupt ONE sampling slot's
                # logits at the host boundary (the compiled program is never
                # perturbed, so co-tenant state stays bitwise clean) and
                # derail its sampled token the way a real NaN argmax would
                victims = [i for i in sampling
                           if spec.rid is None
                           or self.slots[i]["rid"] == spec.rid]
                if victims:
                    vi = victims[-1]
                    logits_np = np.asarray(out["logits"]).copy()
                    logits_np[vi, :] = (np.nan if spec.mode == "nan"
                                        else np.inf)
                    nxt[vi] = 0
        if self.sc.nan_guard and sampling and logits_np is None:
            logits_np = np.asarray(out["logits"])

        poisoned: set[int] = set()
        if self.sc.nan_guard and logits_np is not None:
            poisoned = {i for i in sampling
                        if not np.isfinite(logits_np[i]).all()}

        for i in active:
            slot = self.slots[i]
            slot["fed"] += n_tok[i]
            if slot["fed"] < len(slot["seq"]):
                continue                        # still prefilling
            if i in poisoned:
                # fail the poisoned slot and release its blocks instead of
                # sampling garbage into its stream; co-tenants are untouched
                self._fail_request(slot["rid"], EngineError(
                    f"request {slot['rid']}: non-finite decode logits "
                    f"at tick {self._tick_no}", site="tick.logits",
                    tick=self._tick_no, rid=slot["rid"]))
                continue
            tok = int(nxt[i])
            slot["out"].append(tok)
            slot["last"] = tok
            slot["handle"]._append(tok)
            self.tokens_out += 1
            limit = self.sc.max_len - len(slot["prompt"]) - 1
            if slot["max_new"] is not None:
                limit = min(limit, slot["max_new"])
            if tok == self.eos or len(slot["out"]) >= limit:
                self.done[slot["rid"]] = slot["out"]
                slot["handle"]._finish()
                self._release(i, cache_prefix=True)
        return sum(s is not None for s in self.slots) \
            + len(self.scheduler.waiting)

    def pending(self) -> int:
        return sum(s is not None for s in self.slots) \
            + len(self.scheduler.waiting)

    def run_until_done(self, max_ticks: int = 10_000) -> dict[int, list[int]]:
        for _ in range(max_ticks):
            if self.tick() == 0:
                break
        return self.done

    def stats(self) -> dict:
        s = {"ticks": self.ticks, "tokens_out": self.tokens_out,
             "peak_active": self.peak_active,
             "scheduler": self.scheduler.stats(),
             "step_programs": len(self._steps),
             "health": self.health()}
        if self.pool is not None:
            s["pool"] = self.pool.check()
        if self.has_kv and self.kv_traffic["ticks"]:
            n = self.kv_traffic["ticks"]
            s["kv_traffic"] = {
                "mode": self.sc.paged_attention,
                "ticks": n,
                "gather_bytes_per_tick": self.kv_traffic["gather_bytes"] / n,
                "native_bytes_per_tick": self.kv_traffic["native_bytes"] / n,
                "reduction": (self.kv_traffic["gather_bytes"]
                              / max(self.kv_traffic["native_bytes"], 1)),
            }
        if self.prefix_enabled:
            s["prefix_cache"] = self.prefix.stats()
        if self.injector is not None:
            s["faults_fired"] = self.injector.fired()
        if self.executor is not None and self.executor.profile_error:
            s["profile_error"] = self.executor.profile_error
        return s


class AsyncServingEngine:
    """Background tick loop around a PagedServingEngine.

    `submit()` enqueues from any thread and returns the streaming handle
    immediately; a daemon thread ticks whenever work is pending and parks on
    a condition variable when idle.  `drain()` waits for in-flight requests
    to finish and stops the loop; the engine can also be used as a context
    manager (`with AsyncServingEngine(...) as eng: ...` drains on exit).

    Fault tolerance: the engine's tick() already isolates per-request
    failures; if a tick still raises (an engine bug past the isolation
    layer), the loop records it as the TERMINAL error, fails every
    outstanding handle via the engine's degraded transition, notifies all
    waiters, and exits -- `drain()` then raises that terminal error instead
    of spinning into a bare TimeoutError, and `health()` reports the
    state."""

    def __init__(self, cfg: ArchConfig | None = None, params=None,
                 sc: ServeConfig | None = None, *,
                 engine: PagedServingEngine | None = None, **kw):
        if engine is None:
            engine = PagedServingEngine(cfg, params, sc, **kw)
        self.engine = engine
        self._cond = threading.Condition()
        self._running = False
        self._error: BaseException | None = None   # terminal loop error
        self._thread: threading.Thread | None = None

    def start(self) -> "AsyncServingEngine":
        with self._cond:
            if self._running:
                return self
            self._running = True
            self._thread = threading.Thread(target=self._loop,
                                            name="serve-tick", daemon=True)
            self._thread.start()
        return self

    def _loop(self) -> None:
        while True:
            with self._cond:
                while self._running and self.engine.pending() == 0:
                    self._cond.notify_all()          # wake drain() waiters
                    self._cond.wait(timeout=0.05)
                if not self._running:
                    self._cond.notify_all()
                    return
            # tick OUTSIDE the lock: submissions only append to the
            # scheduler's deque, which tick consumes on its next admission
            try:
                self.engine.tick()
            except BaseException as exc:  # noqa: BLE001 -- loop must not die silently
                with self._cond:
                    self._error = exc
                    try:
                        self.engine._enter_degraded(
                            exc if isinstance(exc, EngineError)
                            else EngineError(f"tick loop died: {exc}",
                                             site="engine.loop"))
                    except Exception:     # noqa: BLE001 -- best-effort teardown
                        pass
                    self._running = False
                    self._cond.notify_all()
                return
            with self._cond:
                # every tick changes pending()/queue occupancy: wake drain()
                # and any submit() blocked on backpressure
                self._cond.notify_all()

    def health(self) -> dict:
        h = self.engine.health()
        if self._error is not None:
            h["loop_error"] = self._error
        if self._thread is not None and not self._thread.is_alive():
            h["loop_alive"] = False
        return h

    def submit(self, prompt: list[int], rid: int | None = None,
               max_new_tokens: int | None = None,
               deadline_s: float | None = None,
               queue_timeout: float | None = None) -> RequestHandle:
        """Thread-safe submit.  On a full bounded queue (QueueFull):
        `queue_timeout=None` re-raises immediately (explicit backpressure);
        a number blocks up to that many seconds for the queue to shrink,
        then raises."""
        if self._thread is None:
            self.start()
        deadline = (None if queue_timeout is None
                    else time.monotonic() + queue_timeout)
        with self._cond:
            while True:
                if self._error is not None:
                    raise self._error
                try:
                    handle = self.engine.submit(
                        prompt, rid=rid, max_new_tokens=max_new_tokens,
                        deadline_s=deadline_s)
                except QueueFull:
                    if deadline is None:
                        raise
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        raise
                    continue
                self._cond.notify_all()
                return handle

    def drain(self, timeout: float | None = None) -> dict[int, list[int]]:
        """Graceful stop: wait for all in-flight work, then halt the loop.
        Raises the loop's TERMINAL error (not a bare TimeoutError) when the
        tick thread died with work still pending."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self.engine.pending() > 0:
                if self._error is not None:
                    raise self._error
                if self._running and (self._thread is None
                                      or not self._thread.is_alive()):
                    raise self._error or RuntimeError(
                        "serve-tick thread died with work pending")
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    h = self.engine.health()
                    raise TimeoutError(
                        f"drain timed out with {self.engine.pending()} "
                        f"requests pending (engine {h['state']}, "
                        f"{h['ticks_since_progress']} ticks since progress)")
                self._cond.wait(remaining if remaining is not None else 0.1)
        self.close()
        return self.engine.done

    def close(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if self.engine.state == "healthy":
            self.engine.state = "stopped"

    def __enter__(self) -> "AsyncServingEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.drain()
        else:
            self.close()
