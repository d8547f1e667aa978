"""Host spans over the compiler and the executor (repro.spans).

Contract under test:
  * a span adds calls, seconds and self seconds (its time less that of the
    spans opened inside it) to the process-wide table,
  * a compile's PassRecord seconds are its `pass/<name>` spans' seconds,
  * `verdict_measure` fires once per uncached lowering site and
    `autotune` once per uncached tile search,
  * a compiled training step called twice under the profiler shows one
    `kitsune:run` per call, one `kitsune:program` per plan executable, and
    the programs' names on the XLA modules it launches.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

import repro
from repro import spans
from repro.configs import get_config
from repro.core.executor import _StepSpec, verdict_cache
from repro.core.lower import lower_pipelines
from repro.kernels.autotune import autotune
from repro.optim import adamw
from repro.train import TrainConfig, compile_train_step, make_train_state


def _calls(name: str) -> int:
    return spans.totals().get(name, {}).get("calls", 0)


class TestTable:
    def test_nesting_self_time_and_totals(self):
        spans.reset()
        with spans.span("outer") as outer:
            time.sleep(0.02)
            for _ in range(2):
                with spans.span("inner"):
                    time.sleep(0.01)
        t = spans.totals()
        assert t["outer"]["calls"] == 1 and t["inner"]["calls"] == 2
        assert t["outer"]["seconds"] == outer.seconds
        assert t["outer"]["seconds"] >= 0.04
        assert t["inner"]["self_seconds"] == t["inner"]["seconds"]
        assert t["outer"]["self_seconds"] == pytest.approx(
            t["outer"]["seconds"] - t["inner"]["seconds"], abs=1e-9)
        assert t["outer"]["self_seconds"] >= 0.02
        assert len(spans.durations("inner")) == 2
        assert sum(spans.durations("inner")) == pytest.approx(
            t["inner"]["seconds"])
        assert spans.durations("never") == []
        spans.reset()
        assert spans.totals() == {}

    def test_a_raising_body_still_closes_its_span(self):
        before = _calls("raises")
        with pytest.raises(ValueError):
            with spans.span("raises"):
                raise ValueError("x")
        assert _calls("raises") == before + 1
        with spans.span("after") as sp:
            pass
        # the stack is balanced: the next span is a root, all self time
        assert spans.totals()["after"]["self_seconds"] == pytest.approx(
            sp.seconds)

    def test_durations_keep_the_latest(self):
        for _ in range(spans.KEEP + 3):
            with spans.span("many"):
                pass
        assert len(spans.durations("many")) == spans.KEEP


def test_pass_records_read_their_spans():
    def fn(x, w):
        return jnp.tanh(x @ w) @ w.T

    x = jnp.ones((8, 16))
    w = jnp.ones((16, 16)) * 0.1
    spans.reset()
    app = repro.compile(fn, (x, w), mode="kitsune")
    t = spans.totals()
    names = [r.name for r in app.pass_records]
    assert names[0] == "trace"
    for r in app.pass_records:
        row = t[f"pass/{r.name}"]
        assert row["calls"] == 1
        assert row["seconds"] == r.seconds


def _mlp_graph(m, d, h):
    g = repro.Graph(f"spans_mlp_{m}_{d}_{h}")
    g.input("x", (m, d), "float32")
    g.linear("fc1", "x", h)
    g.elementwise("act", ["fc1"], "gelu")
    g.linear("fc2", "act", d)
    g.output("y", "fc2")
    return g


def test_verdict_measure_once_per_uncached_site():
    members = {"sf0": ["fc1", "act", "fc2"]}
    # shapes no other test uses, so the verdict cache starts cold here;
    # under interpret mode every measurable site is measured
    g = _mlp_graph(8, 24, 40)
    before, size = _calls("verdict_measure"), len(verdict_cache())
    plan = lower_pipelines(g, members, policy="auto")
    assert len(verdict_cache()) == size + 1
    assert _calls("verdict_measure") == before + 1
    (m,) = plan.pipelines["sf0"].matches
    assert m.verdict.source == "measured"
    lower_pipelines(_mlp_graph(8, 24, 40), members, policy="auto")
    assert _calls("verdict_measure") == before + 1, "cached site measured"


def test_autotune_once_per_uncached_key():
    def build(cand):
        return lambda x: x * cand["b"]

    args = (jnp.ones(8),)
    cands = [{"b": 1}, {"b": 2}]
    before = _calls("autotune")
    key = ("spans-test", time.perf_counter_ns())
    choice = autotune(key, cands, build, args, kernel="toy")
    assert choice["b"] in (1, 2) and choice["us"] >= 0
    assert _calls("autotune") == before + 1
    autotune(key, cands, build, args, kernel="toy")
    assert _calls("autotune") == before + 1, "a cache hit searched again"
    autotune(key + ("other",), cands, build, args, kernel="toy")
    assert _calls("autotune") == before + 2
    # one candidate: nothing to search
    autotune(key + ("one",), cands[:1], build, args, kernel="toy")
    assert _calls("autotune") == before + 2


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                          for ev in line.events if "kitsune" in ev.name)
    return events


def test_traced_train_step_names_its_runs_programs_and_modules(tmp_path):
    cfg = get_config("qwen1.5-32b").reduced()
    opt = adamw(1e-3)
    state = make_train_state(cfg, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                          cfg.vocab)}
    app = compile_train_step(cfg, opt, TrainConfig(remat=True, xent_chunk=8),
                             state=state, batch=batch,
                             compile_mode="kitsune")
    engine = app._engine
    programs = [s.prog.name for s in engine._steps if type(s) is _StepSpec]
    inline = [s for s in engine._steps
              if type(s) is not _StepSpec and s.node.kind != "output"]
    assert programs and inline
    compiles = _calls("compile_program")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            state, m = app(state, batch)
            jax.block_until_ready(m["loss"])
    finally:
        jax.profiler.stop_trace()
    # struct-equal programs share one executable: at most one compile each
    assert 0 < _calls("compile_program") - compiles <= len(programs)

    events = _host_events(str(tmp_path))
    runs = sorted((s, e, st["call"]) for n, s, e, st in events
                  if n == "kitsune:run")
    assert [c for _, _, c in runs] == [0, 1]
    for s, e, _ in runs:
        inside = [(n, st) for n, ps, pe, st in events
                  if n.startswith("kitsune:") and s < ps and pe <= e]
        launched = [st["program"] for n, st in inside
                    if n == "kitsune:program"]
        assert sorted(launched) == sorted(programs)
        assert sum(1 for n, _ in inside if n == "kitsune:inline") == \
            len(inline)
        assert sum(1 for n, _ in inside if n == "kitsune:feeds") == 1
        assert sum(1 for n, _ in inside if n == "kitsune:outputs") == 1
    # the second call runs the prebound plan: at most 100 spans a step
    s, e, _ = runs[1]
    assert sum(1 for n, ps, pe, _ in events
               if n.startswith("kitsune:") and s <= ps and pe <= e) <= 100
    # each launched executable is named after its program
    # each executable is named after its program, or a shared one after
    # a program of its structural class
    names = {n for n, *_ in events}
    classes: dict = {}
    for p in programs:
        classes.setdefault(engine.struct_keys.get(p, p), []).append(p)
    for members in classes.values():
        assert any(f"jit(kitsune.{p})" in n for p in members
                   for n in names), members
