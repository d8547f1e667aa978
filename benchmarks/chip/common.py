"""What every driver shares: the cell's files, logging, host spans, the
compile counter, the profiler window and the device readings."""
from __future__ import annotations

import contextlib
import importlib
import json
import sys
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))
OUT_DIR = CHECKOUT / ".bench_out"


def log(msg: str) -> None:
    print(msg, flush=True)


def seed_key(seed: int, stream: str):
    """A JAX key from a seed of any size and a stream name: the low and high
    32 bits of the seed and the name's CRC each fold in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(stream.encode()))


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads in the process
    (JAX's monitoring events), so a window can prove it compiled nothing."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.n += 1


@dataclass
class Context:
    cell: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: bool
    fault: str | None
    t0: float
    device: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)

    @classmethod
    def load(cls, root: Path, cell: str, **kw) -> "Context":
        wl = json.loads((root / "workloads" / f"{cell}.json").read_text())
        cfg = json.loads((root / "configs" / f"{wl['config']}.json")
                         .read_text())
        tr = json.loads((root / "traffic" / f"{wl['traffic']}.json")
                        .read_text())
        return cls(cell=cell, workload=wl, config=cfg, traffic=tr, **kw)

    def peak_for(self, kind: str) -> dict:
        table = json.loads((HERE / "peaks.json").read_text())
        if kind not in table["devices"]:
            if self.device.get("platform") == "cpu":
                return {}                  # tests: no device metric exists
            raise KeyError(f"device_kind {kind!r} is not in peaks.json")
        return table["devices"][kind]

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept in memory with its perf_counter bounds, and
        written into the profiler's trace when one is running."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            yield
        self.spans.append((name, t, time.perf_counter()))

    def ref_arch(self) -> dict:
        """The sizes the reference reads: the run's `arch`, the published
        norm epsilon, and the whole published config as `published` (layer
        types, window, routing)."""
        return dict(self.config["arch"],
                    rms_norm_eps=self.config["published"]["rms_norm_eps"],
                    published=self.config["published"])

    def reference(self):
        """The module `reference/<name>.py` that the configuration names;
        exits non-zero with the name where there is none."""
        name = self.config["reference"]
        if not (name.isidentifier()
                and (HERE / "reference" / f"{name}.py").is_file()):
            sys.exit(f"run.py: configuration {self.config['name']!r} names "
                     f"the reference {name!r}, and there is no "
                     f"reference/{name}.py")
        return importlib.import_module(f"reference.{name}")

    def arch(self):
        """The program's ArchConfig from the configuration file's `arch`."""
        from repro.configs.base import ArchConfig
        return ArchConfig(**self.config["arch"])


def memory_peak_bytes() -> int | None:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()) or None


@contextlib.contextmanager
def profiled(ctx: Context):
    """Profile the block when the run is traced; yields a dict that holds
    the trace's directory afterwards (or nothing)."""
    import jax
    box: dict = {}
    if not ctx.trace:
        yield box
        return
    d = OUT_DIR / f"trace-{ctx.cell}"
    if d.exists():
        import shutil
        shutil.rmtree(d)
    d.mkdir(parents=True)
    jax.profiler.start_trace(str(d))
    try:
        yield box
    finally:
        jax.profiler.stop_trace()
        box["dir"] = d


def check(value: float, limit: float) -> dict:
    return {"value": value, "limit": limit}


def all_within(checks: dict) -> bool:
    return all(c["value"] == c["value"] and c["value"] <= c["limit"]
               for c in checks.values())
