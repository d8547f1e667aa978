"""The trace reduction, checked without a chip: on hand-made events whose
answer is known, and on a small trace recorded on one TPU v5e
(`data/trace_v5e_swiglu.json`: `load()`'s events of three steps, each
one fused SwiGLU kernel call at m=256, d=512, f=1024 and one XLA program).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce as T  # noqa: E402
import run as R  # noqa: E402

MS = 1_000_000


def _events():
    # window 0..100 ms; ops [10, 30) and [20, 40) overlap, [60, 70) alone
    return {"host": [["window", 0, 100 * MS], ["step", 0, 50 * MS],
                     ["step", 50 * MS, 100 * MS], ["wait", 75 * MS, 95 * MS]],
            "devices": {"/device:TPU:0": {
                "ops": [["a", "fused_mlp_fwd", 10 * MS, 30 * MS],
                        ["b", "fusion.1", 20 * MS, 40 * MS],
                        ["c", "fused_mlp_fwd", 60 * MS, 70 * MS],
                        ["d", "late", 120 * MS, 130 * MS]],
                "modules": [["m", 10 * MS, 40 * MS], ["m", 60 * MS, 70 * MS],
                            ["m", 120 * MS, 130 * MS]]}}}


def test_busy_is_the_union_and_idle_its_complement():
    r = T.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.04)        # 10..40 and 60..70
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["programs"] == 2


def test_breakdown_of_ops_and_gaps():
    r = T.reduce(_events())
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fused_mlp_fwd"] == pytest.approx(0.03)
    # gaps: 0..10 (step), 40..60 (step), 70..100 (wait covers 75..95)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["wait", pytest.approx(0.03)]
    assert sorted(round(s, 6) for _, s in gaps) == [0.01, 0.02, 0.03]


DW = ('%tpu_custom_call.7 = (f32[8,16]{1,0:T(8,128)}, f32[8,16]{1,0}, '
      'f32[16,8]{1,0}) custom-call(bf16[4,8]{1,0:T(8,128)(2,1)} %a, '
      'bf16[8,16]{1,0} %b, bf16[8,16]{1,0} %c, bf16[16,8]{1,0} %d, '
      'bf16[4,8]{1,0} %e), custom_call_target="tpu_custom_call", '
      'operand_layout_constraints={bf16[4,8]{1,0}}')


def test_signature_and_short_label_of_a_custom_call():
    sig = T.signature(DW)
    assert sig["target"] == "tpu_custom_call"
    assert [d for _, d in sig["outputs"]] == [(8, 16), (8, 16), (16, 8)]
    assert [d for _, d in sig["operands"]] == [
        (4, 8), (8, 16), (8, 16), (16, 8), (4, 8)]
    assert T.call_bytes(sig) == 4 * 3 * 128 + 2 * (2 * 32 + 3 * 128)
    assert T.short_label(DW) == ("%tpu_custom_call.7 = (f32[8,16], "
                                 "f32[8,16], f32[16,8]) custom-call")
    assert T.signature("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)") is None


def _reader(name):
    return R.load_module(HERE.parent / "metrics" / f"{name}.py", "m")


def test_recorded_trace():
    ev = json.loads((HERE / "data" / "trace_v5e_swiglu.json").read_text())
    r = T.reduce(ev)
    (_, w0, w1), = [h for h in ev["host"] if h[0] == "window"]
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    # six programs ran; the device clock puts the first two before the
    # window's start on the host clock
    assert r["programs"] == 4
    rec = {"trace": r, "shapes": {"m": 256, "d": 512, "f": 1024},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    calls = T.kernel_calls(r, lambda s: s["target"] == "tpu_custom_call")
    assert sum(c for _, c, _ in calls) == 2
    share = _reader("fused_mlp_roofline.train").read(rec)
    assert 0 < share <= 100
    rec["shapes"]["f"] = 2048              # no call of these shapes
    assert _reader("fused_mlp_roofline.train").read(rec) is None


def test_load_finds_the_benchmark_spans(tmp_path):
    """A profile recorded here (no device plane on the CPU) still yields the
    benchmark's host spans, nested as they were opened."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench:step"):
                jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    ev = T.load(tmp_path)
    names = sorted(n for n, _, _ in ev["host"])
    assert names == ["step", "step", "window"]
    (_, w0, w1), = [h for h in ev["host"] if h[0] == "window"]
    assert all(w0 <= s <= e <= w1 for n, s, e in ev["host"] if n == "step")
    r = T.reduce(ev)
    assert r["busy_s"] == 0.0 and r["window_s"] > 0
