"""Pallas kernel tests (interpreter mode on CPU; same code compiles on
TPU).  Oracle: the plain fused attention in bigdl_tpu.nn.attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.ops import flash_attention


def _qkv(b=2, h=2, t=64, d=32, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d).astype(np.float32), dtype)
    return mk(), mk(), mk()


class TestFlashForward:
    def test_matches_reference(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_causal_matches_reference(self):
        q, k, v = _qkv(seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unaligned_t_padding(self):
        """T not divisible by the block sizes exercises the pad/mask path."""
        q, k, v = _qkv(t=50, seed=2)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unaligned_causal(self):
        q, k, v = _qkv(t=37, seed=3)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_custom_scale(self):
        q, k, v = _qkv(seed=4)
        out = flash_attention(q, k, v, scale=0.5, block_q=32, block_k=32)
        ref = dot_product_attention(q, k, v, scale=0.5)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_tk_gt_tq(self):
        """Tk != Tq: key mask must use the KEY length (regression)."""
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(2, 2, 16, 32).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 2, 64, 32).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 2, 64, 32).astype(np.float32))
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_causal_alignment(self):
        """Causal with Tk > Tq uses bottom-right alignment like the
        reference attention."""
        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(1, 2, 24, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_tk_lt_tq(self):
        rng = np.random.RandomState(10)
        q = jnp.asarray(rng.randn(1, 1, 48, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 1, 20, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 1, 20, 16).astype(np.float32))
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        q, k, v = _qkv(t=16, seed=5)
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestFlashBackward:
    def test_grads_match_reference(self):
        q, k, v = _qkv(t=32, seed=6)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_causal_grads(self):
        q, k, v = _qkv(t=32, seed=7)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=16, block_k=16) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestFlashBackwardCross:
    def test_causal_tq_gt_tk_grads(self):
        """Regression: rows with NO visible keys (causal, Tq > Tk) must
        get zero attention in the backward too; a loss with non-zero
        cotangent on those rows exposed p=exp(_NEG - _NEG)=1."""
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, 1, 48, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 1, 20, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 1, 20, 16).astype(np.float32))

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
            return jnp.sum((o - 1.0) ** 2)  # do != 0 on masked rows

        def loss_ref(q, k, v):
            o = dot_product_attention(q, k, v, causal=True)
            return jnp.sum((o - 1.0) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestMhaIntegration:
    def test_mha_flash_path(self):
        from bigdl_tpu import nn

        mha = nn.MultiHeadAttention(32, 4, causal=True,
                                    attention_impl="flash").build(seed=1)
        mha_ref = nn.MultiHeadAttention(32, 4, causal=True).build(seed=1)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 24, 32), jnp.float32)
        out = mha.f(mha.params, x)
        ref = mha_ref.f(mha_ref.params, x)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestPallasBackwardKernel:
    """The VJPs now run the tiled Pallas backward; these pin it against
    the O(T^2) XLA recomputation oracle kept in _flash_bwd_reference."""

    def test_kernel_matches_reference_vjp(self):
        from bigdl_tpu.ops.flash_attention import (_flash_bwd,
                                                   _flash_bwd_reference,
                                                   _flash_fwd)
        q, k, v = _qkv(t=50, seed=20)
        o, lse = _flash_fwd(q, k, v, None, None, True, 0.25, 16, 16, True)
        do = jnp.asarray(np.random.RandomState(21).randn(*o.shape),
                         jnp.float32)
        dlse = jnp.asarray(np.random.RandomState(22).randn(*lse.shape),
                           jnp.float32)
        got = _flash_bwd(q, k, v, o, lse, do, dlse, None, None, True, 0.25, 16, 16, True)
        want = _flash_bwd_reference(True, 0.25, (q, k, v, o, lse), do, dlse)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_kernel_matches_reference_cross(self):
        from bigdl_tpu.ops.flash_attention import (_flash_bwd,
                                                   _flash_bwd_reference,
                                                   _flash_fwd)
        rng = np.random.RandomState(23)
        q = jnp.asarray(rng.randn(1, 2, 24, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
        o, lse = _flash_fwd(q, k, v, None, None, True, 0.25, 16, 16, True)
        do = jnp.asarray(rng.randn(*o.shape), jnp.float32)
        dlse = jnp.zeros(lse.shape, jnp.float32)
        got = _flash_bwd(q, k, v, o, lse, do, dlse, None, None, True, 0.25, 16, 16, True)
        want = _flash_bwd_reference(True, 0.25, (q, k, v, o, lse), do)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_lse_cotangent_end_to_end(self):
        """Loss using BOTH o and lse (the ring-attention merge shape)
        against an explicit XLA attention."""
        from bigdl_tpu.ops import flash_attention_with_lse
        q, k, v = _qkv(t=32, d=16, seed=24)
        scale = 1.0 / np.sqrt(16)

        def loss_flash(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                              block_q=16, block_k=16)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

        def loss_ref(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            t = q.shape[2]
            cmask = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(cmask, s, -jnp.inf)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd",
                           jax.nn.softmax(s, axis=-1), v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestSegmentedFlash:
    """Packed-document isolation: segment_ids mask attention across
    document boundaries inside the flash tiles.  Oracle: the plain XLA
    attention with the equivalent explicit (B, 1, Tq, Tk) mask."""

    @staticmethod
    def _segs(b, t, n_docs, seed):
        rng = np.random.RandomState(seed)
        # random document boundaries -> non-decreasing segment ids
        cuts = np.sort(rng.choice(np.arange(1, t), size=n_docs - 1,
                                  replace=False))
        seg = np.zeros((b, t), np.int32)
        for c in cuts:
            seg[:, c:] += 1
        # vary across batch: roll each row by a different offset's worth
        # of documents
        for i in range(1, b):
            seg[i] = (seg[i] + i) % n_docs
        return jnp.asarray(seg)

    @staticmethod
    def _mask(seg):
        return (seg[:, None, :, None] == seg[:, None, None, :])

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_masked_reference(self, causal):
        q, k, v = _qkv(t=64, seed=30)
        seg = self._segs(2, 64, 4, 31)
        out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, causal=causal,
                                    mask=self._mask(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unaligned_t(self):
        """T not a block multiple: the -1/-2 segment pad fills must
        never match each other or any real id."""
        q, k, v = _qkv(t=53, seed=32)
        seg = self._segs(2, 53, 3, 33)
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, causal=True,
                                    mask=self._mask(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grads_match_masked_reference(self):
        q, k, v = _qkv(t=48, seed=34)
        seg = self._segs(2, 48, 3, 35)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=True, segment_ids=seg,
                                block_q=16, block_k=16)
            return jnp.sum((o - 1.0) ** 2)  # nonzero do everywhere

        def loss_ref(q, k, v):
            o = dot_product_attention(q, k, v, causal=True,
                                      mask=self._mask(seg))
            return jnp.sum((o - 1.0) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_single_segment_is_vanilla(self):
        """All-one-segment ids must reproduce unsegmented attention."""
        q, k, v = _qkv(t=32, seed=36)
        seg = jnp.zeros((2, 32), jnp.int32)
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              block_q=16, block_k=16)
        ref = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_rejected(self):
        q, k, v = _qkv(t=32, seed=37)
        with pytest.raises(ValueError, match="self-attention"):
            flash_attention(q, k[:, :, :16], v[:, :, :16],
                            segment_ids=jnp.zeros((2, 32), jnp.int32))


class TestSegmentedFlashFuzz:
    """Seeded sweep: random shapes, block sizes, and segment patterns
    (including degenerate all-one-doc and every-position-its-own-doc)
    against the masked-XLA oracle — broader assurance than the fixed
    configs above."""

    @pytest.mark.parametrize("style", ["few", "many", "one", "singletons"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_random_configs_match_oracle(self, style, seed):
        # style is parametrized explicitly so the degenerate patterns are
        # GUARANTEED to run, not left to what four seeds happen to draw
        styles = ["few", "many", "one", "singletons"]
        r = np.random.RandomState(seed * 7 + styles.index(style))
        b = int(r.randint(1, 3))
        h = int(r.choice([1, 2, 4]))
        t = int(r.choice([32, 48, 96]))
        d = int(r.choice([16, 32]))
        bq = int(r.choice([16, 32]))
        bk = int(r.choice([16, 32]))
        causal = bool(r.randint(2))
        if style == "one":
            seg = np.zeros((b, t), np.int32)
        elif style == "singletons":
            seg = np.tile(np.arange(t, dtype=np.int32), (b, 1))
        else:
            n_docs = 3 if style == "few" else max(2, t // 8)
            seg = np.sort(r.randint(0, n_docs, (b, t)).astype(np.int32))
        seg = jnp.asarray(seg)
        mk = lambda: jnp.asarray(r.randn(b, h, t, d), jnp.float32)
        q, k, v = mk(), mk(), mk()
        got = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=bq, block_k=bk)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        want = dot_product_attention(q, k, v, causal=causal, mask=mask)
        # singletons + non-causal: every row still sees itself; fully
        # defined either way
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
