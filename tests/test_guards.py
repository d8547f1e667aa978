"""Guards on the way to the chip: no CPU fallback in chip_smoke.py, no
interpret-mode default, the compile-cache location, refused autotune
candidates, and failures that must propagate instead of degrading."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.lower import kernel_config
from repro.kernels import KernelConfig, autotune
from repro.launch import cache
from repro.models import get_model
from repro.serve import ServeConfig
from repro.serve import engine as engine_mod

REPO = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


class TestKernelConfig:
    def test_no_interpret_default(self):
        assert KernelConfig().interpret is None
        with pytest.raises(ValueError, match="interpret"):
            KernelConfig(use_pallas=True)

    def test_probe_picks_interpret_off_tpu(self):
        cfg = kernel_config()
        assert cfg.use_pallas and cfg.interpret and not cfg.autotune


class TestCompileCache:
    def test_env_dir_left_to_jax(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        before = jax.config.jax_compilation_cache_dir
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_repo_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = cache.enable_compile_cache()
            assert got == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestAutotuneRefusals:
    @staticmethod
    def build(cand):
        def fn(x):
            if cand["block"] == 0:
                raise ValueError("tile refused by the compiler")
            return x * cand["block"]
        return fn

    def test_refused_candidate_skipped(self):
        args = (jnp.ones((8, 128)),)
        choice = autotune(("guard-skip",), [{"block": 0}, {"block": 2}],
                          self.build, args)
        assert choice["block"] == 2
        assert len(choice["refused"]) == 1
        assert choice["refused"][0].startswith("block=0: ")

    def test_all_refused_raises(self):
        args = (jnp.ones((8, 128)),)
        with pytest.raises(RuntimeError, match="refused all 2"):
            autotune(("guard-all",), [{"block": 0}, {"block": 0}],
                     self.build, args)


def test_profile_run_propagates_compile_errors(monkeypatch):
    cfg = get_config("gemma3-1b").reduced()
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    ex = engine_mod.PagedKVExecutor(cfg, params,
                                    ServeConfig(max_len=16, batch=2))
    assert ex.profile_run() > 0

    def refused(*a, **k):
        raise RuntimeError("tick refused by the backend")

    monkeypatch.setattr(engine_mod, "paged_tick", refused)
    with pytest.raises(RuntimeError, match="refused by the backend"):
        ex.get_max_allowed_kv_blocks()
