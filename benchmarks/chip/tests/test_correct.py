"""The checks that decide `correct`, on the CPU at a test size.

Each run goes through `run.run` with the chip look skipped: the same
driver, window, reference and comparison as a run on the chip, on the tiny
configurations under `tests/data`, whose limits sit between the clean
program's readings and the control's at this size.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as R  # noqa: E402

DATA = HERE / "data"
SEED = 2**33 + 11


def _run(cell: str, *, fault=None, control=False, seconds=4.0) -> dict:
    argv = ["--workload", cell, "--seed", str(SEED), "--seconds",
            str(seconds)] + (["--control"] if control else [])
    return R.run(R.parse(argv), root=DATA, bench_path=DATA / "BENCHMARK.json",
                 check_device=False, fault=fault)


@pytest.mark.parametrize("cell", ["tiny-train-jit", "tiny-train-kitsune"])
def test_clean_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_training_control_is_not_correct():
    res = _run("tiny-train-jit", control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny-train-kitsune", "state_unchanged"),
    ("tiny-train-kitsune", "half_tokens"),
    ("tiny-train-kitsune", "answer_altered"),
])
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    res = _run(cell, fault=fault)
    assert not res["correct"], res["checks"]
