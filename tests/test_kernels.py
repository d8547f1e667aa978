"""Per-kernel validation: Pallas (interpret=True) vs ref.py oracles, sweeping
shapes/dtypes, plus gradient checks for the fused_mlp custom_vjp."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (KernelConfig, attention, attention_bwd,
                           decode_attention, mlp, mlp_swiglu, reduce)
from repro.kernels import ref
from repro.kernels.flash_attention import (combine_partials, flash_attention,
                                           flash_attention_bwd,
                                           flash_attention_lse, flash_decode,
                                           train_block)
from repro.kernels.fused_mlp import fused_mlp_bwd, fused_mlp_fwd, fused_mlp_swiglu_fwd
from repro.kernels.queue_reduce import queue_reduce

KC = KernelConfig(use_pallas=True, interpret=True)


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused MLP
# ---------------------------------------------------------------------------

class TestFusedMLP:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("m,d,h,o", [
        (128, 64, 512, 64),     # canonical
        (256, 128, 1024, 96),   # rectangular out
        (128, 32, 512, 32),     # small feature dims
    ])
    def test_fwd_matches_ref(self, m, d, h, o, dtype):
        x, w1, w2 = rand(0, (m, d), dtype), rand(1, (d, h), dtype), rand(2, (h, o), dtype)
        got = fused_mlp_fwd(x, w1, w2, act="gelu", block_m=128, block_h=256,
                            interpret=True)
        want = ref.mlp_ref(x, w1, w2, "gelu")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    @pytest.mark.parametrize("act", ["gelu", "relu", "silu", "identity"])
    def test_activations(self, act):
        x, w1, w2 = rand(0, (128, 32), jnp.float32), rand(1, (32, 256), jnp.float32), rand(2, (256, 32), jnp.float32)
        got = fused_mlp_fwd(x, w1, w2, act=act, block_m=128, block_h=128, interpret=True)
        want = ref.mlp_ref(x, w1, w2, act)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("block_h", [128, 256, 512])
    def test_hidden_tiling_invariance(self, block_h):
        """The spatial split of the hidden dim must not change the result."""
        x, w1, w2 = rand(0, (128, 64), jnp.float32), rand(1, (64, 512), jnp.float32), rand(2, (512, 64), jnp.float32)
        got = fused_mlp_fwd(x, w1, w2, act="gelu", block_m=128,
                            block_h=block_h, interpret=True)
        want = ref.mlp_ref(x, w1, w2, "gelu")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_swiglu_fwd(self):
        d, h, o = 64, 512, 64
        x = rand(0, (128, d), jnp.float32)
        wg, wu, wd = rand(1, (d, h), jnp.float32), rand(2, (d, h), jnp.float32), rand(3, (h, o), jnp.float32)
        got = fused_mlp_swiglu_fwd(x, wg, wu, wd, block_m=128, block_h=128, interpret=True)
        want = ref.mlp_swiglu_ref(x, wg, wu, wd)
        # hidden-dim tiling changes f32 summation order; outputs are O(1e3)
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)

    def test_bwd_matches_autodiff(self):
        """Fig 2(c) multicast backward == jax.grad of the reference."""
        m, d, h, o = 128, 32, 256, 48
        x, w1, w2 = rand(0, (m, d), jnp.float32), rand(1, (d, h), jnp.float32), rand(2, (h, o), jnp.float32)
        dy = rand(3, (m, o), jnp.float32)

        def loss(x, w1, w2):
            return jnp.sum(ref.mlp_ref(x, w1, w2, "gelu") * dy)

        want = jax.grad(loss, argnums=(0, 1, 2))(x, w1, w2)
        got = fused_mlp_bwd(x, w1, w2, dy, act="gelu", block_m=128,
                            block_h=128, interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-3, atol=5e-3)

    def test_custom_vjp_wrapper(self):
        m, d, h, o = 128, 32, 256, 32
        x, w1, w2 = rand(0, (m, d), jnp.float32), rand(1, (d, h), jnp.float32), rand(2, (h, o), jnp.float32)

        def f_pallas(x, w1, w2):
            return jnp.sum(jnp.square(mlp(x, w1, w2, act="gelu", cfg=KC)))

        def f_ref(x, w1, w2):
            return jnp.sum(jnp.square(ref.mlp_ref(x, w1, w2, "gelu")))

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(x, w1, w2)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, w1, w2)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3)

    def test_leading_batch_dims(self):
        x = rand(0, (4, 32, 64), jnp.float32)
        w1, w2 = rand(1, (64, 256), jnp.float32), rand(2, (256, 64), jnp.float32)
        got = mlp(x, w1, w2, cfg=KC)
        want = ref.mlp_ref(x.reshape(-1, 64), w1, w2, "gelu").reshape(4, 32, 64)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @settings(max_examples=8, deadline=None)
    @given(mi=st.integers(1, 4), d=st.sampled_from([32, 64]),
           hmul=st.integers(1, 4))
    def test_shape_property(self, mi, d, hmul):
        m, h = mi * 128, hmul * 128
        x, w1, w2 = rand(7, (m, d), jnp.float32), rand(8, (d, h), jnp.float32), rand(9, (h, d), jnp.float32)
        got = fused_mlp_fwd(x, w1, w2, act="relu", block_m=128, block_h=128,
                            interpret=True)
        np.testing.assert_allclose(got, ref.mlp_ref(x, w1, w2, "relu"),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, causal, dtype):
        b, h, s, d = 2, 4, 256, 64
        q, k, v = (rand(i, (b, h, s, d), dtype) for i in range(3))
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    def test_gqa_groups(self):
        b, hq, hkv, s, d = 2, 8, 2, 128, 32
        q = rand(0, (b, hq, s, d), jnp.float32)
        k, v = rand(1, (b, hkv, s, d), jnp.float32), rand(2, (b, hkv, s, d), jnp.float32)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        """gemma3-style local attention."""
        b, h, s, d = 1, 2, 256, 32
        q, k, v = (rand(i, (b, h, s, d), jnp.float32) for i in range(3))
        got = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
    def test_block_invariance(self, bq, bk):
        b, h, s, d = 1, 2, 256, 32
        q, k, v = (rand(i, (b, h, s, d), jnp.float32) for i in range(3))
        got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @settings(max_examples=6, deadline=None)
    @given(s=st.sampled_from([128, 256]), d=st.sampled_from([32, 64]),
           hq=st.sampled_from([2, 4]), grp=st.sampled_from([1, 2]))
    def test_gqa_property(self, s, d, hq, grp):
        hkv = hq // grp
        q = rand(11, (1, hq, s, d), jnp.float32)
        k, v = rand(12, (1, hkv, s, d), jnp.float32), rand(13, (1, hkv, s, d), jnp.float32)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


class TestFlashAttentionTraining:
    """The training kernels -- forward with lse, and the dQ / dK-dV pair --
    against `jax.vjp` of the jnp chunked attention the training atoms
    replace, with the window as a runtime int32 scalar."""

    @pytest.mark.parametrize("hq,hkv,causal,window", [
        (4, 4, True, None),        # causal
        (8, 2, True, None),        # GQA, group 4
        (4, 1, True, 20),          # a window that restricts
        (4, 4, True, 1000),        # a window wider than S
        (2, 2, False, None),       # bidirectional (encoder self-attention)
    ], ids=["causal", "gqa4", "window", "wide_window", "noncausal"])
    def test_matches_chunked_vjp(self, hq, hkv, causal, window):
        from repro.models.lm import HUGE_WINDOW, chunked_attention
        b, s, d, blk = 1, 48, 16, 16
        q = rand(20, (b, hq, s, d), jnp.float32)
        k, v = (rand(21 + i, (b, hkv, s, d), jnp.float32) for i in range(2))
        do = rand(23, (b, hq, s, d), jnp.float32)
        w = jnp.asarray(HUGE_WINDOW if window is None else window, jnp.int32)
        kw = dict(causal=causal, window=w, block_q=blk, block_k=blk,
                  interpret=True)

        def chunked(q_, k_, v_):
            return chunked_attention(q_, k_, v_, causal=causal, window=w,
                                     chunk=16)

        want_o, pull = jax.vjp(chunked, q, k, v)
        o, lse = flash_attention_lse(q, k, v, **kw)
        np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
        # the row log-sum-exp of the scaled, masked scores
        kk = jnp.repeat(k, hq // hkv, axis=1)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * d ** -0.5
        qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = (qi - ki) < w
        if causal:
            mask &= qi >= ki
        want_lse = jax.nn.logsumexp(jnp.where(mask, sc, -jnp.inf), axis=-1)
        np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
        got = flash_attention_bwd(q, k, v, do, **kw)
        for name, g, want in zip("qkv", got, pull(do)):
            assert g.shape == want.shape and g.dtype == want.dtype
            np.testing.assert_allclose(g, want, rtol=2e-5, atol=2e-5,
                                       err_msg=f"d{name}")

    def test_bf16_operands_keep_dtype(self):
        """The cell's dtype: bf16 matmul operands, f32 statistics, outputs
        in the primals' dtype."""
        from repro.models.lm import chunked_attention
        b, hq, hkv, s, d = 1, 4, 1, 32, 16
        q = rand(30, (b, hq, s, d), jnp.bfloat16)
        k, v = (rand(31 + i, (b, hkv, s, d), jnp.bfloat16) for i in range(2))
        do = rand(33, (b, hq, s, d), jnp.bfloat16)
        _, pull = jax.vjp(lambda *a: chunked_attention(*a, causal=True),
                          q, k, v)
        got = flash_attention_bwd(q, k, v, do, causal=True, block_q=16,
                                  block_k=16, interpret=True)
        for g, want in zip(got, pull(do)):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_train_block_rule(self):
        assert train_block(2048) == 512
        assert train_block(768) == 256
        assert train_block(640) == 128
        assert train_block(12) == 12
        assert train_block(1000) is None


class TestFlashDecode:
    @pytest.mark.parametrize("s,valid", [(512, 512), (512, 300), (1024, 17)])
    def test_split_k_decode(self, s, valid):
        b, hq, hkv, d = 2, 8, 2, 64
        q = rand(0, (b, hq, 1, d), jnp.float32)
        k, v = rand(1, (b, hkv, s, d), jnp.float32), rand(2, (b, hkv, s, d), jnp.float32)
        got = flash_decode(q, k, v, valid_len=valid, block_s=256, interpret=True)
        want = ref.decode_ref(q, k, v, valid_len=valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_combine_partials_is_exact_softmax(self):
        """Splitting softmax into chunks + merging == unsplit softmax."""
        key = jax.random.PRNGKey(3)
        s = jax.random.normal(key, (4, 6, 256))
        # full softmax-weighted value
        vvals = jax.random.normal(jax.random.PRNGKey(4), (4, 6, 256, 16))
        p = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("bhk,bhkd->bhd", p, vvals)
        # chunked partials
        chunks = 4
        sc = s.reshape(4, 6, chunks, 64)
        vc = vvals.reshape(4, 6, chunks, 64, 16)
        m = jnp.max(sc, axis=-1)                        # (4,6,chunks)
        e = jnp.exp(sc - m[..., None])
        l = jnp.sum(e, axis=-1)
        o = jnp.einsum("bhck,bhckd->bhcd", e, vc)
        got = combine_partials(o.transpose(0, 2, 1, 3),
                               m.transpose(0, 2, 1)[..., None],
                               l.transpose(0, 2, 1)[..., None], axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# queue reduce
# ---------------------------------------------------------------------------

class TestQueueReduce:
    @pytest.mark.parametrize("op", ["sum", "max", "min"])
    @pytest.mark.parametrize("n,r,c", [(8, 128, 64), (3, 256, 32), (16, 128, 128)])
    def test_matches_ref(self, op, n, r, c):
        x = rand(0, (n, r, c), jnp.float32)
        got = queue_reduce(x, op=op, interpret=True)
        want = ref.reduce_ref(x, op)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_bfloat16(self):
        x = rand(0, (8, 128, 64), jnp.bfloat16)
        got = queue_reduce(x, op="sum", interpret=True)
        want = ref.reduce_ref(x, "sum")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @settings(max_examples=8, deadline=None)
    @given(n=st.integers(1, 12), rb=st.integers(1, 3))
    def test_reduction_property(self, n, rb):
        x = rand(5, (n, rb * 128, 32), jnp.float32)
        got = queue_reduce(x, op="sum", interpret=True)
        np.testing.assert_allclose(got, x.sum(axis=0), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ops-level dispatch
# ---------------------------------------------------------------------------

class TestOpsDispatch:
    def test_mlp_pallas_vs_xla_paths_agree(self):
        x = rand(0, (64, 32), jnp.float32)  # m=64 not 128-divisible: pad path
        w1, w2 = rand(1, (32, 128), jnp.float32), rand(2, (128, 32), jnp.float32)
        a = mlp(x, w1, w2, cfg=KernelConfig(use_pallas=False))
        b = mlp(x, w1, w2, cfg=KC)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    def test_attention_bwd_dispatch(self):
        """The backward's two paths -- the kernel pair and the vjp of the
        oracle -- agree, with a runtime window."""
        q = rand(0, (1, 4, 32, 16), jnp.float32)
        k, v = rand(1, (1, 2, 32, 16), jnp.float32), rand(2, (1, 2, 32, 16), jnp.float32)
        dy = rand(3, (1, 4, 32, 16), jnp.float32)
        w = jnp.asarray(9, jnp.int32)
        kc = KernelConfig(use_pallas=True, interpret=True, block_q=16,
                          block_k=16)
        a = attention_bwd(q, k, v, dy, window=w,
                          cfg=KernelConfig(use_pallas=False))
        b = attention_bwd(q, k, v, dy, window=w, cfg=kc)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-4, atol=2e-4)

    def test_decode_dispatch(self):
        q = rand(0, (1, 4, 1, 32), jnp.float32)
        k, v = rand(1, (1, 2, 256, 32), jnp.float32), rand(2, (1, 2, 256, 32), jnp.float32)
        a = decode_attention(q, k, v, valid_len=100, cfg=KernelConfig(use_pallas=False))
        b = decode_attention(q, k, v, valid_len=100, cfg=KC)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# activation derivatives (closed forms used inside the backward kernels)
# ---------------------------------------------------------------------------

class TestActDerivatives:
    @pytest.mark.parametrize("act", ["gelu", "relu", "silu", "identity"])
    def test_dact_matches_jax_grad(self, act):
        """_DACTS holds closed forms (the gelu one replaced a per-element
        vmap(grad) that was catastrophically slow); differential-test every
        entry against jax.grad of the matching forward activation."""
        from repro.kernels.fused_mlp import _ACTS, _DACTS
        x = jnp.linspace(-6.0, 6.0, 513, dtype=jnp.float32)
        if act == "relu":
            x = x[jnp.abs(x) > 1e-3]  # grad undefined at exactly 0
        got = _DACTS[act](x)
        want = jax.vmap(jax.grad(lambda t: _ACTS[act](t)))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_dgelu_is_closed_form(self):
        """The gelu derivative must not be built from jax.grad (tracing a
        grad per element is what made the old version pathological)."""
        from repro.kernels import fused_mlp
        names = fused_mlp._dgelu.__code__.co_names
        assert "grad" not in names and "vmap" not in names, names
        assert fused_mlp._DACTS["gelu"] is fused_mlp._dgelu

    def test_swiglu_identity_act_is_plain_gate_mul(self):
        """act='identity' turns the SwiGLU kernel into gate*up -- the form
        the lower_kernels pass targets for builder dual-GEMM blocks."""
        d, h, o = 32, 128, 32
        x = rand(0, (64, d), jnp.float32)
        wg, wu, wd = (rand(1, (d, h), jnp.float32),
                      rand(2, (d, h), jnp.float32),
                      rand(3, (h, o), jnp.float32))
        got = fused_mlp_swiglu_fwd(x, wg, wu, wd, act="identity",
                                   block_m=64, block_h=128, interpret=True)
        want = ((x @ wg) * (x @ wu)) @ wd
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
