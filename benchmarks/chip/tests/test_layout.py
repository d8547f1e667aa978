"""The weights' layout and the FLOP count, read through the reference module
that each configuration names (`reference/<name>.py`), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as R  # noqa: E402
import weights  # noqa: E402
from common import Context  # noqa: E402

DATA = HERE / "data"
SEED = 2**33 + 11
DRIVER = R.load_module(HERE.parent / "drivers" / "train.py", "t_train")
# sha256 of tiny-dense's program tree at SEED (leaf paths, dtypes, shapes
# and bytes), as the weights were made before layouts moved into the
# reference modules
TINY_DENSE_DIGEST = \
    "243104faa9a83b94ce12b859bc1b8497918cef4bfcff5dd1e8003856d134aad6"


def _ctx(root: Path, cell: str) -> Context:
    return Context.load(root, cell, seed=SEED, seconds=0.0, trace=False,
                        control=False, fault=None, t0=0.0)


def _program_shapes(cfg):
    from repro.models import get_model
    return jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        x = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


def test_dense_weights_are_as_before():
    ctx = _ctx(DATA, "tiny-train-kitsune")
    shapes = _program_shapes(ctx.arch())
    tree = weights.program_tree(SEED, ctx.ref_arch(), ctx.reference(),
                                len(shapes["blocks"]))
    weights.check_layout(tree, shapes)
    assert _digest(tree) == TINY_DENSE_DIGEST


def test_dense_flops_per_token_as_before():
    ctx = _ctx(R.HERE, "phi3m-train-kitsune")
    ref, a = ctx.reference(), ctx.ref_arch()
    assert ref.matmul_params(a) == 845_742_080
    assert ref.train_flops_per_token(a, ctx.traffic["seq"]) == 5_200_343_040


# --- two kinds of layer: the program's dense / MoE stack, moe_period 2 ---

def _moe_arch():
    from repro.configs.base import ArchConfig
    return ArchConfig(name="tiny-moe", family="moe", n_layers=4, d_model=64,
                      n_heads=2, n_kv_heads=1, head_dim=32, d_ff=48,
                      vocab=128, n_experts=4, top_k=2, moe_period=2,
                      dense_d_ff=96, tie_embeddings=False)


def _stub_reference():
    """Declares both kinds of layer by the program's names: even layers
    dense, odd layers experts."""
    def top_shapes(a):
        return {"embed": (a["vocab"], a["d_model"]),
                "final_norm": (a["d_model"],),
                "unembed": (a["vocab"], a["d_model"])}

    def layer_shapes(a, i):
        d, e = a["d_model"], a["n_experts"]
        q, kv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
        shapes = {"ln1": (d,), "attn/wq": (d, q), "attn/wk": (d, kv),
                  "attn/wv": (d, kv), "attn/wo": (q, d), "ln2": (d,)}
        if i % 2 == 0:
            f = a["dense_d_ff"]
            shapes.update({"mlp/wg": (d, f), "mlp/wu": (d, f),
                           "mlp/wd": (f, d)})
        else:
            f = a["d_ff"]
            shapes.update({"moe/router": (d, e),
                           "moe/experts/wg": (e, d, f),
                           "moe/experts/wu": (e, d, f),
                           "moe/experts/wd": (e, f, d)})
        return shapes

    return SimpleNamespace(top_shapes=top_shapes, layer_shapes=layer_shapes)


@pytest.fixture(scope="module")
def moe():
    cfg = _moe_arch()
    a = dict(vars(cfg))
    ref = _stub_reference()
    shapes = _program_shapes(cfg)
    subs = len(shapes["blocks"])
    return a, ref, shapes, subs, weights.program_tree(SEED, a, ref, subs)


def test_two_kinds_of_layer_take_the_programs_layout(moe):
    a, ref, shapes, subs, tree = moe
    assert subs == 2
    weights.check_layout(tree, shapes)


@pytest.mark.parametrize("layer", range(4))
def test_reference_layer_is_its_slice_of_the_program_tree(moe, layer):
    a, ref, _, subs, tree = moe
    got = weights.layer_params(SEED, a, layer, ref, jnp.bfloat16)
    sub = DRIVER._flat_blocks(tree["blocks"][f"sub{layer % subs}"])
    assert set(got) == set(sub)
    for name, x in got.items():
        want = np.asarray(sub[name][layer // subs])
        assert np.array_equal(np.asarray(x).view(np.uint16),
                              want.view(np.uint16)), name


def test_stacked_norms_name_every_leaf_of_every_sub_once_per_layer(moe):
    a, ref, _, _, tree = moe
    norms = DRIVER._stacked_norms(tree)
    want = list(ref.top_shapes(a)) + [
        f"{k}@{i}" for i in range(a["n_layers"]) for k in ref.layer_shapes(a, i)]
    assert sorted(norms) == sorted(want)
    for i in (1, 3):
        w = weights.layer_params(SEED, a, i, ref)["moe/experts/wd"]
        assert float(norms[f"moe/experts/wd@{i}"]) == pytest.approx(
            float(jnp.linalg.norm(w)), rel=1e-5)


def test_a_leaf_with_no_init_scale_is_refused():
    ref = SimpleNamespace(top_shapes=lambda a: {},
                          layer_shapes=lambda a, i: {"mix/lambda": (4,)})
    with pytest.raises(ValueError, match="lambda"):
        weights.program_tree(SEED, {"n_layers": 1}, ref, 1)


@pytest.mark.parametrize("control", [False, True])
def test_unknown_reference_exits_with_its_name_before_any_weights(
        tmp_path, monkeypatch, control):
    for d in ("workloads", "traffic", "configs"):
        shutil.copytree(DATA / d, tmp_path / d)
    path = tmp_path / "configs" / "tiny-dense.json"
    cfg = json.loads(path.read_text())
    cfg["reference"] = "no_such_reference"
    path.write_text(json.dumps(cfg))

    def made(*_, **__):
        raise AssertionError("weights made before the reference was found")

    for fn in ("program_tree", "layer_params", "top_params"):
        monkeypatch.setattr(weights, fn, made)
    argv = ["--workload", "tiny-train-jit", "--seed", str(SEED),
            "--seconds", "1"] + (["--control"] if control else [])
    with pytest.raises(SystemExit) as e:
        R.run(R.parse(argv), root=tmp_path,
              bench_path=DATA / "BENCHMARK.json", check_device=False)
    assert "no_such_reference" in str(e.value.code)
