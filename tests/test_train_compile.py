"""Dataflow training end-to-end: the full train step (forward, backward,
loss, optimizer) compiled through the pass pipeline.

Contract under test:
  * `compile_train_step` traces fwd+bwd+optimizer into the Graph IR and the
    compiled step matches raw `jax.grad`+optimizer over MULTIPLE steps
    (params, optimizer state and loss) on >= 3 zoo architectures,
  * the MLP blocks lower onto EXECUTABLE fused kernels in both directions
    (`fused_mlp`/`fused_mlp_swiglu` forward, `fused_mlp_bwd` backward --
    not the plan-only analysis of synthesized graphs),
  * the backward Pallas kernels (two-matrix and gated) match `jax.grad`
    in interpret mode,
  * donation safety: only the declared state argument's dead buffers are
    donated (never batch feeds, never aliased buffers), and donated state
    is actually consumed,
  * the zero-relowering hot-path contract holds for training plans.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import get_config
from repro.core.executor import lowering_count
from repro.kernels import KernelConfig, mlp_bwd, mlp_swiglu_bwd, ref
from repro.optim import adamw
from repro.train import (TrainConfig, compile_train_step, make_train_state,
                         make_train_step)

# gemma3: swiglu + local/global window schedule; whisper: encoder-decoder
# with two-matrix gelu MLPs (the literal fused_mlp_bwd kernel); qwen: plain
# dense swiglu decoder.
TRAIN_ARCHS = ["gemma3-1b", "whisper-small", "qwen1.5-32b"]

_TC = TrainConfig(remat=False, xent_chunk=8)


def _case(name, seed=0, batch=2, seq=12):
    cfg = get_config(name).reduced()
    opt = adamw(1e-3)
    state = make_train_state(cfg, opt, jax.random.PRNGKey(seed))
    data = {"tokens": jax.random.randint(jax.random.PRNGKey(seed + 1),
                                         (batch, seq), 0, cfg.vocab)}
    if cfg.family == "encdec":
        data["frame_embeds"] = jax.random.normal(
            jax.random.PRNGKey(seed + 2), (batch, seq, cfg.d_model),
            jnp.float32)
    return cfg, opt, state, data


def _copy(tree):
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


def _assert_tree_close(want, got, label, rtol=1e-3, atol=1e-3):
    wl = jax.tree_util.tree_leaves(want)
    gl = jax.tree_util.tree_leaves(got)
    assert len(wl) == len(gl), label
    for w, g in zip(wl, gl):
        np.testing.assert_allclose(
            np.asarray(w, np.float32), np.asarray(g, np.float32),
            rtol=rtol, atol=atol, err_msg=label)


def _kernels(app):
    out = {}
    for p in app.lowering.pipelines.values():
        for m in p.matches:
            out.setdefault(m.kernel, []).append(m)
    return out


# --------------------------------------------------------------------------
# backward kernels vs jax.grad (interpret mode)
# --------------------------------------------------------------------------

class TestBackwardKernels:
    @pytest.mark.parametrize("act", ["gelu", "relu", "silu", "identity"])
    def test_mlp_bwd_matches_autodiff(self, act):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (16, 8), jnp.float32)
        w1 = jax.random.normal(ks[1], (8, 32), jnp.float32) * 0.1
        w2 = jax.random.normal(ks[2], (32, 8), jnp.float32) * 0.1
        dy = jax.random.normal(ks[3], (16, 8), jnp.float32)
        f = lambda x, w1, w2: jnp.vdot(ref.mlp_ref(x, w1, w2, act=act), dy)
        want = jax.grad(f, argnums=(0, 1, 2))(x, w1, w2)
        for cfg in (KernelConfig(),
                    KernelConfig(use_pallas=True, interpret=True)):
            got = mlp_bwd(x, w1, w2, dy, act=act, cfg=cfg)
            for w, g in zip(want, got):
                np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4,
                                           err_msg=f"{act} pallas={cfg.use_pallas}")

    def test_swiglu_bwd_matches_autodiff(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        x = jax.random.normal(ks[0], (16, 8), jnp.float32)
        wg = jax.random.normal(ks[1], (8, 32), jnp.float32) * 0.1
        wu = jax.random.normal(ks[2], (8, 32), jnp.float32) * 0.1
        wd = jax.random.normal(ks[3], (32, 8), jnp.float32) * 0.1
        dy = jax.random.normal(ks[4], (16, 8), jnp.float32)
        f = lambda *a: jnp.vdot(ref.mlp_swiglu_ref(*a, act="silu"), dy)
        want = jax.grad(f, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
        for cfg in (KernelConfig(),
                    KernelConfig(use_pallas=True, interpret=True)):
            got = mlp_swiglu_bwd(x, wg, wu, wd, dy, act="silu", cfg=cfg)
            for w, g in zip(want, got):
                np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)

    def test_mlp_bwd_leading_batch_dims(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        x = jax.random.normal(ks[0], (2, 8, 8), jnp.float32)
        w1 = jax.random.normal(ks[1], (8, 16), jnp.float32) * 0.1
        w2 = jax.random.normal(ks[2], (16, 8), jnp.float32) * 0.1
        dy = jax.random.normal(ks[3], (2, 8, 8), jnp.float32)
        dx, dw1, dw2 = mlp_bwd(x, w1, w2, dy, act="gelu",
                               cfg=KernelConfig(use_pallas=True,
                                                interpret=True))
        assert dx.shape == x.shape
        assert dw1.shape == w1.shape and dw2.shape == w2.shape


# --------------------------------------------------------------------------
# compiled train step vs raw jax.grad + optimizer (>= 3 zoo archs)
# --------------------------------------------------------------------------

class TestTrainDifferential:
    @pytest.mark.parametrize("name", TRAIN_ARCHS)
    def test_multistep_matches_raw(self, name):
        cfg, opt, state, batch = _case(name)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 compile_mode="kitsune", donate_state=True)
        # the backward MLP lowers as an EXECUTABLE kernel, not plan-only
        kern = _kernels(app)
        bwd = kern.get("fused_mlp_bwd", [])
        assert bwd, f"{name}: no fused_mlp_bwd matches"
        assert all(m.executable for m in bwd), f"{name}: plan-only backward"
        fwd = kern.get("fused_mlp", []) + kern.get("fused_mlp_swiglu", [])
        assert fwd and all(m.executable for m in fwd)

        raw = jax.jit(make_train_step(cfg, opt, _TC))
        rstate = _copy(state)
        s = state  # consumed by donation; the app returns the next state
        for i in range(3):
            s, m = app(s, batch)
            rstate, rm = raw(rstate, batch)
            np.testing.assert_allclose(float(m["loss"]), float(rm["loss"]),
                                       rtol=1e-4, err_msg=f"{name} step {i}")
        _assert_tree_close(rstate["params"], s["params"], f"{name} params")
        _assert_tree_close(rstate["opt"], s["opt"], f"{name} opt state")

    def test_attention_lowers_both_directions_matches_raw(self):
        """A dense step whose attention sites pass their (roofline)
        verdicts: the forward and backward attention atoms run the
        flash-attention kernels, and losses, parameters and optimizer state
        still match raw jax.grad over several steps."""
        cfg, opt, state, batch = _case("qwen1.5-32b", seed=9, seq=16)
        app = compile_train_step(cfg, opt, _TC, state=_copy(state),
                                 batch=batch, donate_state=False,
                                 lowering_policy="cost")
        kern = _kernels(app)
        for label in ("flash_attention", "flash_attention_bwd"):
            sites = kern.get(label, [])
            assert len(sites) == cfg.n_layers, (label, len(sites))
            assert all(m.executable and m.accepted for m in sites), label
        raw = jax.jit(make_train_step(cfg, opt, _TC))
        s, rstate = state, _copy(state)
        for i in range(3):
            s, m = app(s, batch)
            rstate, rm = raw(rstate, batch)
            np.testing.assert_allclose(float(m["loss"]), float(rm["loss"]),
                                       rtol=1e-4, err_msg=f"step {i}")
        _assert_tree_close(rstate["params"], s["params"], "params")
        _assert_tree_close(rstate["opt"], s["opt"], "opt state")

    def test_bsp_mode_same_numerics(self):
        cfg, opt, state, batch = _case("gemma3-1b", seed=3)
        kit = compile_train_step(cfg, opt, _TC, state=_copy(state),
                                 batch=batch, compile_mode="kitsune",
                                 donate_state=False)
        bsp = compile_train_step(cfg, opt, _TC, state=_copy(state),
                                 batch=batch, compile_mode="bsp",
                                 donate_state=False)
        ks, km = kit(state, batch)
        bs, bm = bsp(state, batch)
        np.testing.assert_allclose(float(km["loss"]), float(bm["loss"]),
                                   rtol=1e-5)
        _assert_tree_close(bs, ks, "kitsune vs bsp state", rtol=5e-4,
                           atol=5e-4)

    def test_second_step_zero_relowering(self):
        cfg, opt, state, batch = _case("qwen1.5-32b", seed=4)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 donate_state=True)
        s, _ = app(state, batch)
        before = lowering_count()
        s, _ = app(s, batch)
        assert lowering_count() == before, "training hot path re-lowered"


# --------------------------------------------------------------------------
# donation safety
# --------------------------------------------------------------------------

class TestDonationSafety:
    def _donated_feed_slots(self, app):
        eng = app._engine
        slots = set()
        for spec in eng._steps:
            donate = getattr(spec, "donate", ())
            for p in donate:
                slots.add(spec.prog.needs[p])
        return slots

    def test_only_declared_state_feeds_donated(self):
        cfg, opt, state, batch = _case("gemma3-1b", seed=5)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 donate_state=True)
        donated = self._donated_feed_slots(app)
        feed_donated = donated & app.donate_feeds
        assert feed_donated, "no state buffer is donated"
        # batch feeds and consts are NEVER in the donate set
        n_state_leaves = len(jax.tree_util.tree_leaves(state))
        assert len(app.donate_feeds) == n_state_leaves
        batch_leaves = len(jax.tree_util.tree_leaves(batch))
        all_args = app.traced.in_names
        batch_names = set(all_args[n_state_leaves:
                                   n_state_leaves + batch_leaves])
        assert not (donated & batch_names), "batch buffers donated"

    def test_donate_state_false_donates_no_feeds(self):
        cfg, opt, state, batch = _case("gemma3-1b", seed=6)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 donate_state=False)
        assert not app.donate_feeds
        donated = self._donated_feed_slots(app)
        assert not (donated & set(app.traced.in_names)), \
            "undeclared feed donated"

    def test_donated_state_is_consumed(self):
        cfg, opt, state, batch = _case("qwen1.5-32b", seed=7)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 donate_state=True)
        app(state, batch)
        leaves = jax.tree_util.tree_leaves(state)
        assert any(getattr(x, "is_deleted", lambda: False)() for x in leaves), \
            "donation declared but no state buffer was consumed"

    def test_aliased_feed_buffers_never_donated(self):
        """Two feed names sharing ONE buffer (e.g. tied state leaves) must
        not be donated: donating one name would invalidate the other."""
        def step(state, x):
            return {"a": state["a"] + x, "b": state["b"] * 2.0}

        shared = jnp.ones((8, 8), jnp.float32)
        state = {"a": shared, "b": shared}      # aliased on purpose
        x = jnp.ones((8, 8), jnp.float32)
        app = repro.compile(step, (state, x), mode="bsp",
                            donate_argnums=(0,))
        out = app(state, x)                      # must not crash
        np.testing.assert_allclose(np.asarray(out["a"]), 2.0)
        np.testing.assert_allclose(np.asarray(out["b"]), 2.0)
        assert not shared.is_deleted(), "aliased buffer was donated"

    def test_optimizer_moments_distinct_buffers(self):
        """adamw.init must allocate m and v separately -- aliased moments
        would silently disable in-place donation of the optimizer state."""
        opt = adamw(1e-3)
        st = opt.init({"w": jnp.ones((4, 4), jnp.float32)})
        m, v = st.inner["w"]
        assert m is not v


# --------------------------------------------------------------------------
# atoms capture (unit level)
# --------------------------------------------------------------------------

class TestTrainingAtoms:
    def test_mlp_atom_grad_lowers_both_directions(self):
        from repro.models.atoms import mlp_atom
        amlp = mlp_atom("gelu")
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (4, 8), jnp.float32)
        w1 = jax.random.normal(ks[1], (8, 16), jnp.float32) * 0.1
        w2 = jax.random.normal(ks[2], (16, 8), jnp.float32) * 0.1
        loss = lambda w1, w2: jnp.sum(amlp(x, w1, w2) ** 2)
        app = repro.compile(jax.grad(loss, argnums=(0, 1)), (w1, w2),
                            mode="kitsune")
        used = app.lowering.kernels_used()
        assert "fused_mlp" in used and "fused_mlp_bwd" in used
        want = jax.grad(
            lambda w1, w2: jnp.sum(ref.mlp_ref(x, w1, w2, act="gelu") ** 2),
            argnums=(0, 1))(w1, w2)
        got = app(w1, w2)
        for w, g in zip(want, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)

    def test_attention_atom_grad_lowers_both_directions(self):
        """GQA attention atom under jax.grad: the forward lowers onto
        flash_attention and the backward onto the flash_attention_bwd pair,
        both executable, with the window a runtime operand."""
        from repro.models.atoms import attention_atom
        from repro.models.lm import chunked_attention
        atom = attention_atom(True, 1024)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 8, 16, 8), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 16, 8), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 16, 8), jnp.float32)
        win = jnp.asarray(6, jnp.int32)
        loss = lambda q, k, v: jnp.sum(atom(q, k, v, win) ** 2)
        app = repro.compile(jax.grad(loss, argnums=(0, 1, 2)), (q, k, v),
                            mode="kitsune", lowering_policy="always")
        kern = _kernels(app)
        for label in ("flash_attention", "flash_attention_bwd"):
            assert kern.get(label), f"no {label} match"
            assert all(m.executable and m.accepted for m in kern[label])
        want = jax.grad(lambda q, k, v: jnp.sum(chunked_attention(
            q, k, v, causal=True, window=win) ** 2), argnums=(0, 1, 2))(
                q, k, v)
        for w, g in zip(want, app(q, k, v)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)

    def test_dataflow_training_restores_originals(self):
        from repro.models import atoms, layers, lm
        orig_mlp, orig_attn = layers.mlp_block, lm.chunked_attention
        with atoms.dataflow_training():
            assert layers.mlp_block is not orig_mlp
            assert lm.chunked_attention is not orig_attn
        assert layers.mlp_block is orig_mlp
        assert lm.chunked_attention is orig_attn

    def test_attention_atom_recompute_backward_matches(self):
        from repro.models.atoms import attention_atom
        from repro.models.lm import chunked_attention
        atom = attention_atom(True, 1024)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 8, 4), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 8, 4), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 8, 4), jnp.float32)
        win = jnp.asarray(1 << 30, jnp.int32)
        f_atom = lambda q, k, v: jnp.sum(atom(q, k, v, win) ** 2)
        f_raw = lambda q, k, v: jnp.sum(
            chunked_attention(q, k, v, causal=True) ** 2)
        want = jax.grad(f_raw, argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(f_atom, argnums=(0, 1, 2))(q, k, v)
        for w, g in zip(want, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# describe() surface for training artifacts
# --------------------------------------------------------------------------

class TestDescribeTraining:
    def test_describe_shows_executable_backward(self):
        cfg, opt, state, batch = _case("whisper-small", seed=8)
        # an encoder longer than the decoder: cross-attention has sq != skv
        batch["frame_embeds"] = jnp.concatenate(
            [batch["frame_embeds"]] * 2, axis=1)
        app = compile_train_step(cfg, opt, _TC, state=state, batch=batch,
                                 donate_state=False)
        text = app.describe()
        assert "lowered fused_mlp_bwd" in text
        # executable backward matches carry no plan-only tag
        for line in text.splitlines():
            if "lowered fused_mlp_bwd" in line:
                assert "(plan-only)" not in line
        # self-attention sites match the flash-attention kernels both ways;
        # only the cross-attention sites fall back, each with its reason
        kern = _kernels(app)
        assert kern.get("flash_attention") and kern.get("flash_attention_bwd")
        g = app.graph
        matched = {o for ms in kern.values() for m in ms for o in m.ops}
        fallbacks = {op: why for p in app.lowering.pipelines.values()
                     for op, why in p.fallbacks.items()}
        n_cross = 0
        for n in g.nodes.values():
            if n.kind != "attention":
                continue
            sq, skv = (g.nodes[i].out.shape[2] for i in n.inputs[:2])
            if sq == skv:
                assert n.name in matched, n.name
            else:
                n_cross += 1
                assert n.name not in matched
                assert "needs sq == skv" in fallbacks[n.name]
        assert n_cross == 2 * cfg.n_layers   # forward and backward
        assert "needs sq == skv" in text
