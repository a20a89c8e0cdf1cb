"""Attention + sequence-parallelism tests.

Oracle: plain dot_product_attention (itself cross-checked against an
explicit softmax).  Ring and Ulysses run on the 8-virtual-device CPU mesh
(conftest) and must match the single-device result exactly (same math,
different schedule).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import blockwise_attention, dot_product_attention
from bigdl_tpu.parallel import (SEQUENCE_AXIS, create_mesh, ring_attention,
                                sequence_parallel_self_attention,
                                ulysses_attention)

B, H, T, D = 2, 8, 64, 16


def _qkv(seed=0, t=T):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, H, t, D), jnp.float32) for _ in range(3))


def _naive(q, k, v, causal=False):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((tq, tk), bool), k=tk - tq)
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_dot_product_attention_matches_naive(causal):
    q, k, v = _qkv()
    got = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), _naive(*map(np.asarray, (q, k, v)),
                                                       causal=causal),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [16, 64, 48, 24])  # 48, 24: T=64 not a multiple -> tail padding
def test_blockwise_matches_plain(causal, block_size):
    q, k, v = _qkv(1)
    want = dot_product_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, block_size=block_size, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_blockwise_grads_match():
    q, k, v = _qkv(2)
    f1 = lambda q, k, v: jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)
    f2 = lambda q, k, v: jnp.sum(
        blockwise_attention(q, k, v, block_size=16, causal=True) ** 2)
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_plain(causal):
    mesh = create_mesh({SEQUENCE_AXIS: 8})
    q, k, v = _qkv(3)
    want = dot_product_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_under_jit_and_grad():
    mesh = create_mesh({SEQUENCE_AXIS: 8})
    q, k, v = _qkv(4)

    @jax.jit
    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_plain(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(float(loss_ring(q, k, v)),
                               float(loss_plain(q, k, v)), rtol=1e-4)
    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_plain(causal):
    mesh = create_mesh({SEQUENCE_AXIS: 8})
    q, k, v = _qkv(5)  # H=8 divisible by axis size 8
    want = dot_product_attention(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_mha_module_shapes_and_cross_attention():
    mha = nn.MultiHeadAttention(32, 4, causal=True).build(seed=0)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 10, 32), jnp.float32)
    y, _ = mha.apply(mha.params, x)
    assert y.shape == (2, 10, 32)
    # cross-attention via tuple and Table input
    from bigdl_tpu.utils.table import T as TT
    kv = jnp.asarray(np.random.RandomState(1).randn(2, 7, 32), jnp.float32)
    mha2 = nn.MultiHeadAttention(32, 4).build(seed=0)
    y2, _ = mha2.apply(mha2.params, (x, kv, kv))
    assert y2.shape == (2, 10, 32)
    y3, _ = mha2.apply(mha2.params, TT(x, kv, kv))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y3))
    # causal: output at t must not depend on inputs after t
    x_mod = x.at[:, 5:, :].set(0.0)
    y_mod, _ = mha.apply(mha.params, x_mod)
    np.testing.assert_allclose(np.asarray(y[:, :5]), np.asarray(y_mod[:, :5]),
                               rtol=1e-5, atol=1e-6)


def test_mha_blockwise_matches_plain_module():
    x = jnp.asarray(np.random.RandomState(2).randn(2, 64, 32), jnp.float32)
    plain = nn.MultiHeadAttention(32, 4, causal=True).build(seed=7)
    blocked = nn.MultiHeadAttention(32, 4, causal=True, block_size=16).build(seed=7)
    y1, _ = plain.apply(plain.params, x)
    y2, _ = blocked.apply(blocked.params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", ["ring", "ulysses"])
def test_sequence_parallel_self_attention_matches_single_device(kind):
    mesh = create_mesh({SEQUENCE_AXIS: 8})
    mha = nn.MultiHeadAttention(32, 8, causal=True).build(seed=3)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 64, 32), jnp.float32)
    want, _ = mha.apply(mha.params, x)
    got = sequence_parallel_self_attention(mha, mha.params, x, mesh, kind=kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-5)


class TestRingFlash:
    """Ring attention with the Pallas flash kernel per hop (impl='flash')."""

    def _inputs(self, t=32, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(rng.randn(2, 2, t, 16).astype(np.float32))
        return mk(), mk(), mk()

    def _mesh(self, n=4):
        from bigdl_tpu.parallel.mesh import SEQUENCE_AXIS, create_mesh
        return create_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])

    def test_matches_plain(self):
        from bigdl_tpu.nn.attention import dot_product_attention
        from bigdl_tpu.parallel import ring_attention

        q, k, v = self._inputs()
        out = ring_attention(q, k, v, self._mesh(), impl="flash", block_size=8)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causal_matches_plain(self):
        from bigdl_tpu.nn.attention import dot_product_attention
        from bigdl_tpu.parallel import ring_attention

        q, k, v = self._inputs(seed=1)
        out = ring_attention(q, k, v, self._mesh(), causal=True,
                             impl="flash", block_size=8)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grads_flow(self):
        from bigdl_tpu.nn.attention import dot_product_attention
        from bigdl_tpu.parallel import ring_attention

        q, k, v = self._inputs(t=16, seed=2)
        mesh = self._mesh(2)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                          impl="flash", block_size=8) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        gp = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gp):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("past", [False, True])
def test_auto_dispatch_rule(monkeypatch, backend, past):
    """"auto" picks flash only on a TPU backend from the crossover length up
    (interpreter-mode flash on CPU is for correctness tests, never speed):
    the platform and the length, either side of ``FLASH_AUTO_MIN_T``."""
    from bigdl_tpu.ops.flash_attention import (FLASH_AUTO_MIN_T,
                                               use_flash_auto)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    t = FLASH_AUTO_MIN_T if past else FLASH_AUTO_MIN_T - 1
    assert use_flash_auto(t) is (backend == "tpu" and past)
    # the module's one rule: "auto" follows it, a pinned block_size or
    # "xla" never takes the kernel, "flash" always
    mha = lambda **kw: nn.MultiHeadAttention(64, 2, causal=True, **kw)  # noqa
    assert mha().resolve_use_flash(t) is use_flash_auto(t)
    assert mha(block_size=64).resolve_use_flash(t) is False
    assert mha(attention_impl="xla").resolve_use_flash(t) is False
    assert mha(attention_impl="flash").resolve_use_flash(t) is True


def test_flash_blocks_left_out_are_128():
    """``flash_attention`` without blocks runs 128 x 128 tiles: the same
    bits as the blocks pinned, other bits than another tile's order of
    sums."""
    from bigdl_tpu.ops import flash_attention
    rs = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.randn(1, 2, 256, 16), jnp.float32)
               for _ in range(3))
    default = flash_attention(q, k, v, causal=True)
    pinned = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_array_equal(np.asarray(default), np.asarray(pinned))
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(default), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


class TestSegmentedSequenceParallel:
    """Packed-document isolation under sequence parallelism: the
    key-side segment shard rides the ring / one small all_gather feeds
    Ulysses — outputs must match single-device masked attention."""

    @staticmethod
    def _segs(t, n_docs, seed):
        r = np.random.RandomState(seed)
        cuts = np.sort(r.choice(np.arange(1, t), n_docs - 1, replace=False))
        seg = np.zeros((B, t), np.int32)
        for c in cuts:
            seg[:, c:] += 1
        return jnp.asarray(seg)

    @staticmethod
    def _mask(seg):
        return (seg[:, None, :, None] == seg[:, None, None, :])

    @pytest.mark.parametrize("impl", ["blocks", "flash"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_segmented_matches_plain(self, impl, causal):
        mesh = create_mesh({SEQUENCE_AXIS: 8})
        q, k, v = _qkv(11)
        seg = self._segs(T, 4, 12)
        want = dot_product_attention(q, k, v, causal=causal,
                                     mask=self._mask(seg))
        got = ring_attention(q, k, v, mesh, causal=causal, impl=impl,
                             segment_ids=seg,
                             block_size=T // 8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_segmented_matches_plain(self, causal):
        from bigdl_tpu.parallel import ulysses_attention
        mesh = create_mesh({SEQUENCE_AXIS: 8})
        q, k, v = _qkv(13)
        seg = self._segs(T, 3, 14)
        want = dot_product_attention(q, k, v, causal=causal,
                                     mask=self._mask(seg))
        got = ulysses_attention(q, k, v, mesh, causal=causal,
                                segment_ids=seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_ring_segmented_grads(self):
        mesh = create_mesh({SEQUENCE_AXIS: 8})
        q, k, v = _qkv(15)
        seg = self._segs(T, 3, 16)

        @jax.jit
        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                          impl="flash", segment_ids=seg,
                                          block_size=T // 8) ** 2)

        def loss_plain(q, k, v):
            return jnp.sum(dot_product_attention(
                q, k, v, causal=True, mask=self._mask(seg)) ** 2)

        g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_mha_segment_ids(impl):
    """nn.MultiHeadAttention.f(segment_ids=...) matches the explicit
    mask through both cores."""
    from bigdl_tpu import nn
    from bigdl_tpu.nn.attention import segment_mask

    mha = nn.MultiHeadAttention(32, 4, causal=True,
                                attention_impl=impl).build(seed=2)
    r = np.random.RandomState(21)
    x = jnp.asarray(r.randn(2, 24, 32), jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(3), 8)[None].repeat(2, 0))
    got = mha.f(mha.params, x, segment_ids=seg)
    q, k, v = mha.project_qkv(mha.params, x, x, x)
    want = mha.project_out(mha.params, dot_product_attention(
        q, k, v, causal=True, mask=segment_mask(seg, seg)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_mha_blockwise_rejects_segments():
    from bigdl_tpu import nn
    mha = nn.MultiHeadAttention(32, 4, causal=True,
                                block_size=8).build(seed=2)
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match="block_size"):
        mha.f(mha.params, x, segment_ids=jnp.zeros((1, 16), jnp.int32))
