"""The program's spans as the benchmark reads them, checked without a chip:
the attribution of idle time on hand-made events whose answer is known,
the readers of the span metrics, and one traced run of the tiny kitsune
cell through `spans_report.py` on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
from __future__ import annotations

import gzip
import json
import statistics
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import program_spans as P  # noqa: E402
import run as R  # noqa: E402
import spans_report  # noqa: E402
import trace_reduce as T  # noqa: E402

MS = 1_000_000
DATA = HERE / "data"
SPAN_METRICS = [
    {"name": "jaxpr_trace_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "compiler", "moves": "setup_s",
     "workloads": ["tiny-train-kitsune"]},
    {"name": "site_measure_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "compiler", "moves": "setup_s",
     "workloads": ["tiny-train-kitsune"]},
    {"name": "program_compile_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "executor", "moves": "setup_s",
     "workloads": ["tiny-train-kitsune"]},
    {"name": "dispatch_ms.train", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "executor",
     "moves": "train_tokens_s", "workloads": ["tiny-train-kitsune"]},
]


def _spans():
    # window 0..100 ms: two steps, each one `kitsune:run` holding feeds,
    # two program launches and an inline op, then a wait on the loss
    return [["window", 0, 100 * MS, {}],
            ["step", 0, 50 * MS, {}], ["step", 50 * MS, 100 * MS, {}],
            ["kitsune:run", 2 * MS, 32 * MS, {"call": 3}],
            ["kitsune:feeds", 2 * MS, 5 * MS, {}],
            ["kitsune:program", 5 * MS, 10 * MS, {"program": "sf0"}],
            ["kitsune:inline", 10 * MS, 20 * MS, {"op": "reshape"}],
            ["kitsune:program", 20 * MS, 30 * MS, {"program": "sf1"}],
            ["kitsune:run", 52 * MS, 62 * MS, {"call": 4}],
            ["kitsune:program", 52 * MS, 56 * MS, {"program": "sf0"}],
            ["kitsune:program", 56 * MS, 60 * MS, {"program": "sf1"}]]


def _devices():
    # the device runs sf0 at [6, 12) and [53, 58), sf1 at [25, 40) and
    # [58, 70); idle: [0,6) [12,25) [40,53) [70,100)
    return {"/device:TPU:0": {
        "ops": [["a", "x", 6 * MS, 12 * MS], ["b", "y", 25 * MS, 40 * MS],
                ["a", "x", 53 * MS, 58 * MS], ["b", "y", 58 * MS, 70 * MS]],
        "modules": [["jit_kitsune.sf0(1)", 6 * MS, 12 * MS],
                    ["jit_kitsune.sf1(2)", 25 * MS, 40 * MS],
                    ["jit_kitsune.sf0(1)", 53 * MS, 58 * MS],
                    ["jit_kitsune.sf1(2)", 58 * MS, 70 * MS],
                    ["jit_reshape(3)", 13 * MS, 14 * MS]]}}


def test_idle_by_innermost_span():
    a = P.attribute(_devices(), _spans())
    assert a["idle_s"] == pytest.approx(0.062)
    # idle [0,6) [12,25) [40,53) [70,100) against the innermost spans
    want = {"step": 0.002 + 0.012 + 0.030,          # 0-2, 40-52, 70-100
            "kitsune:feeds": 0.003,                  # 2-5
            "kitsune:program": 0.001 + 0.005 + 0.001,  # 5-6, 20-25, 52-53
            "kitsune:inline": 0.008}                 # 12-20
    got = a["idle_by_span"]
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k
    assert sum(got.values()) == pytest.approx(a["idle_s"])


def test_run_ms_and_idle_in_run():
    a = P.attribute(_devices(), _spans())
    assert a["run_ms"] == [pytest.approx(30.0), pytest.approx(10.0)]
    # idle inside runs: 2-6 and 12-25 in the first, 52-53 in the second
    assert a["idle_in_run_s"] == pytest.approx(0.004 + 0.013 + 0.001)
    assert a["idle_in_run_s"] <= a["idle_s"]
    assert a["program_host_s"] == {"sf1": pytest.approx(0.014),
                                   "sf0": pytest.approx(0.009)}
    assert a["modules"] == {"jit_kitsune.sf0": 1.0, "jit_kitsune.sf1": 1.0,
                            "jit_reshape": 0.5}


def test_gap_labels_name_program_spans():
    a = P.attribute(_devices(), _spans())
    gaps = a["idle_gaps"]
    assert gaps[0] == ["step", pytest.approx(0.030)]            # 70..100
    assert ["kitsune:inline", pytest.approx(0.013)] in gaps      # 12..25
    assert ["kitsune:feeds", pytest.approx(0.006)] in gaps       # 0..6
    assert all(name != P.OUTSIDE for name, _ in gaps)


def test_without_program_spans_labels_are_the_benchmarks():
    spans = [s for s in _spans() if not s[0].startswith(P.PROGRAM_PREFIX)]
    a = P.attribute(_devices(), spans)
    events = {"host": [s[:3] for s in spans], "devices": _devices()}
    assert a["idle_gaps"] == T.reduce(events)["breakdown"]["idle_gaps"]
    assert a["run_ms"] == [] and a["idle_in_run_s"] == 0
    assert a["skew_ms"] == {}


def test_skew_pairs_launches_with_modules_in_order():
    s = P.attribute(_devices(), _spans())["skew_ms"]
    # launches 5, 20, 52, 56 ms; kitsune modules 6, 25, 53, 58 ms
    assert s["launches"] == 4
    assert s["min"] == pytest.approx(1.0)
    assert s["step_first_min"] == pytest.approx(1.0)
    assert s["step_first_median"] == pytest.approx(1.0)
    assert s["median"] == pytest.approx(1.5)
    assert s["named_share"] == 1.0


def test_skew_survives_a_module_missing_from_the_trace():
    devices = _devices()
    devices["/device:TPU:0"]["modules"].pop(0)      # the first sf0
    s = P.attribute(devices, _spans())["skew_ms"]
    assert s["launches"] == 3 and s["named_share"] == 1.0
    assert s["min"] == pytest.approx(1.0)
    assert s["median"] == pytest.approx(2.0)        # 5, 1, 2 ms


def test_readers_read_nothing_without_the_programs_spans(monkeypatch):
    readers = {m["name"]: R.load_module(
        HERE.parent / "metrics" / f"{m['name']}.py", "t_" + m["name"])
        for m in SPAN_METRICS}
    monkeypatch.setattr(P, "totals", lambda: {})
    monkeypatch.setattr(P, "durations", lambda name: [])
    for name, mod in readers.items():
        monkeypatch.setattr(mod, "totals", P.totals, raising=False)
        monkeypatch.setattr(mod, "durations", P.durations, raising=False)
        assert mod.read({"steps": 3}) is None, name


def test_traced_tiny_cell_through_spans_report(tmp_path, capsys):
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    bench["per_layer"] += SPAN_METRICS
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    saved = tmp_path / "events.json.gz"
    spans_report.main(["--workload", "tiny-train-kitsune", "--seed",
                       str(2**33 + 5), "--seconds", "2", "--trace", "1",
                       "--save", str(saved)],
                      root=DATA, bench_path=path, check_device=False)
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    for m in SPAN_METRICS:
        assert metrics[m["name"]]["value"] > 0, m["name"]
    # no device plane off the chip, so no [idle] or [gap] lines here
    tags = {line.split()[0] for line in out[:-1] if line.startswith("[")}
    assert {"[span]", "[verdict]", "[attrib]"} <= tags
    spans = {line.split()[1] for line in out if line.startswith("[span]")}
    assert {"pass/trace", "compile_program", "run", "program", "feeds",
            "outputs"} <= spans
    # the window's runs are the last `steps` of the program's `run` spans
    attrib = next(line for line in out if line.startswith("[attrib]"))
    steps = int(attrib.split()[2])
    assert steps >= 1
    assert metrics["dispatch_ms.train"]["value"] == pytest.approx(
        1e3 * statistics.median(P.durations("run")[-steps:]))
    # the saved events read back to the same attribution
    with gzip.open(saved, "rt") as f:
        ev = json.load(f)
    again = P.attribute(ev["devices"], ev["spans"])
    assert len(again["run_ms"]) == steps
