"""Paged-decode attention: the Pallas block-table kernel vs the dense
``kc[tables]`` gather.

The kernel reads KV blocks in place through the block table (no dense
gather materialization); its numerics replicate the gather path's exact
formulation (f32 cast -> scaled dot -> -1e30 position mask -> softmax),
so the two are interchangeable mid-stream.  Fast tier-1 coverage: op
equivalence on CPU (interpret mode) across dtypes / scrambled tables /
mid-block positions, and engine-level token-exactness — greedy AND
sampled streams through ``decode_attn="paged_kernel"`` must match
offline ``generate`` bit for bit, with radix sharing on.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.models.transformer.generate import _decode_step_paged, generate
from bigdl_tpu.ops import (autotune, paged_decode_attention,
                           paged_decode_attention_reference)
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.serving.kvcache.blocks import pack_rows


@pytest.fixture(autouse=True)
def _hermetic_tune_cache(tmp_path, monkeypatch):
    """Point the tuning cache at an empty tmp file: the repo-committed
    TUNE_ATTN.json must never steer these tests' dispatch."""
    monkeypatch.setenv("BIGDL_TPU_TUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _arena(slots=3, heads=2, head_dim=8, cache_len=24, block_len=4,
           dtype=jnp.float32, seed=0, shuffle=True):
    """Random q + paged KV arena.  Block ids are shuffled by default —
    non-contiguous tables are the whole point of paging, and a kernel
    that only works on arange tables is wrong."""
    width = -(-cache_len // block_len)
    num_blocks = slots * width + 1  # block 0 is the scratch block
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (slots, heads, head_dim), dtype)
    # one layer's arena in the pool's layout: (N, B, W), a position row
    # holding its heads side by side, lane-padded
    shape = (num_blocks, block_len, heads, head_dim)
    ka = pack_rows(jax.random.normal(ks[1], shape, dtype))
    va = pack_rows(jax.random.normal(ks[2], shape, dtype))
    ids = np.arange(1, slots * width + 1)
    if shuffle:
        np.random.RandomState(seed).shuffle(ids)
    tables = jnp.asarray(ids.reshape(slots, width), jnp.int32)
    return q, ka, va, tables


# --------------------------------------------------------------------------- #
# op equivalence (interpret mode on CPU)                                      #
# --------------------------------------------------------------------------- #

def test_kernel_matches_reference_f32():
    q, ka, va, tables = _arena()
    pos = jnp.asarray([23, 9, 14], jnp.int32)
    out = paged_decode_attention(q, ka, va, tables, pos)
    ref = paged_decode_attention_reference(q, ka, va, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_kernel_matches_reference_bf16_arena():
    q, ka, va, tables = _arena(dtype=jnp.bfloat16, seed=3)
    pos = jnp.asarray([23, 12, 7], jnp.int32)
    out = paged_decode_attention(q, ka, va, tables, pos)
    ref = paged_decode_attention_reference(q, ka, va, tables, pos)
    # both paths cast to f32 BEFORE every matmul; only the bf16 loads
    # differ, so the f32 outputs agree tightly
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mid_block_and_zero_positions_masked_identically():
    """pos mid-block (valid prefix ends inside a page) and pos 0 (a
    single visible token) — the -1e30 mask must hide the same tail."""
    q, ka, va, tables = _arena(seed=1)
    pos = jnp.asarray([5, 0, 17], jnp.int32)
    out = paged_decode_attention(q, ka, va, tables, pos)
    ref = paged_decode_attention_reference(q, ka, va, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_kernel_accepts_4d_query_layout():
    """(S, H, 1, D) — the engine's decode layout — round-trips with the
    singleton axis preserved."""
    q, ka, va, tables = _arena(seed=2)
    pos = jnp.asarray([23, 9, 14], jnp.int32)
    out4 = paged_decode_attention(q[:, :, None, :], ka, va, tables, pos)
    out3 = paged_decode_attention(q, ka, va, tables, pos)
    assert out4.shape == (3, 2, 1, 8)
    np.testing.assert_allclose(np.asarray(out4[:, :, 0, :]),
                               np.asarray(out3), rtol=1e-6, atol=1e-6)


def test_kernel_under_jit():
    q, ka, va, tables = _arena(seed=4)
    pos = jnp.asarray([23, 9, 14], jnp.int32)
    out = jax.jit(paged_decode_attention)(q, ka, va, tables, pos)
    ref = paged_decode_attention_reference(q, ka, va, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_decode_step_rejects_unknown_impl():
    m = _lm()
    with pytest.raises(ValueError, match="attn_impl"):
        _decode_step_paged(m, m.params, jnp.zeros((1,), jnp.int32),
                           jnp.zeros((1,), jnp.int32),
                           jnp.zeros((3, 2), jnp.int32),
                           jnp.zeros((1, 3, 4, 128)),
                           jnp.zeros((1, 3, 4, 128)),
                           table_width=2, attn_impl="nope")


# --------------------------------------------------------------------------- #
# engine-level token exactness                                                #
# --------------------------------------------------------------------------- #

def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=32, seed=0):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers, max_len=max_len,
                         pos_encoding="rope").build(seed=seed)


def test_paged_kernel_stream_token_exact_greedy_and_sampled():
    """ACCEPTANCE: with the Pallas paged-decode kernel live (and radix
    sharing on), greedy AND sampled streams are bit-exact vs offline
    generate — the kernel changes memory traffic, never tokens."""
    m = _lm()
    eng = LMServingEngine(m, slots=2, cache_len=24, block_len=4,
                          prefill_buckets=(4, 8, 16),
                          decode_attn="paged_kernel")
    try:
        assert eng.stats()["decode_attn"] == "paged_kernel"
        p = np.arange(1, 13)  # 3 full blocks: sharing engages
        ref = np.asarray(generate(m, m.params, p[None].astype(np.int32),
                                  6))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=6, timeout=120), ref)
        hits0 = eng.radix.hits
        # identical prompt: served THROUGH the shared chain, still exact
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=6, timeout=120), ref)
        assert eng.radix.hits == hits0 + 1
        sref = np.asarray(generate(
            m, m.params, p[None].astype(np.int32), 6,
            temperature=0.7, rng=jax.random.PRNGKey(7)))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=6, temperature=0.7, rng=7,
                         timeout=120), sref)
    finally:
        eng.close()


def test_dense_gather_still_selectable_and_exact():
    m = _lm()
    eng = LMServingEngine(m, slots=2, cache_len=24, block_len=4,
                          prefill_buckets=(4, 8, 16), decode_attn="gather")
    try:
        assert eng.stats()["decode_attn"] == "gather"
        p = np.arange(1, 10)
        ref = np.asarray(generate(m, m.params, p[None].astype(np.int32),
                                  5))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=5, timeout=120), ref)
    finally:
        eng.close()


def test_auto_resolves_gather_without_tuned_verdict():
    """No cache verdict -> the safe baseline, never the kernel."""
    m = _lm()
    eng = LMServingEngine(m, slots=1, cache_len=24, block_len=4,
                          prefill_buckets=(4,))
    try:
        assert eng.stats()["decode_attn"] == "gather"
    finally:
        eng.close()


def test_auto_resolves_kernel_from_tuned_verdict(tmp_path, monkeypatch):
    """A matching use_kernel=True winner flips "auto" to the kernel."""
    cache = tmp_path / "tuned.json"
    key = autotune.paged_key(8, 4, "float32")  # head_dim 16/2, block 4
    cache.write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "winners": {key: {"use_kernel": True}}}))
    monkeypatch.setenv("BIGDL_TPU_TUNE_CACHE", str(cache))
    autotune.clear_cache()
    m = _lm()
    eng = LMServingEngine(m, slots=1, cache_len=24, block_len=4,
                          prefill_buckets=(4,))
    try:
        assert eng.stats()["decode_attn"] == "paged_kernel"
        p = np.arange(1, 8)
        ref = np.asarray(generate(m, m.params, p[None].astype(np.int32),
                                  4))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=4, timeout=120), ref)
    finally:
        eng.close()


def test_engine_rejects_unknown_decode_attn():
    m = _lm()
    with pytest.raises(ValueError, match="decode_attn"):
        LMServingEngine(m, slots=1, cache_len=24, decode_attn="dense")
