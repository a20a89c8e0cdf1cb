"""The decode attention over a paged pool: which path a pool resolves to
(``generate.decode_attention_path``: ONE function, from the platform and the
pool's shapes), and that the paths are interchangeable mid-stream.

The decision table takes each of the six benchmark configurations
(``benchmarks/configs/*.json``) at its own widths, block length and dtype,
in a pool of two blocks a class, on the CPU and seen as a TPU.  The engine
tests hold greedy AND sampled streams through ``decode_attn="paged_kernel"``
(a ``(k, v)`` pool at a group of ONE query head a K/V head: the grouped
kernel, interpreted) to offline ``generate`` token for token, radix sharing
on.  The kernels against the walk: tests/test_grouped_attention.py,
tests/test_latent_attention.py.
"""
import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import LayerSpec, TransformerLM
from bigdl_tpu.models.transformer.generate import (_decode_step_paged,
                                                   decode_attention_path,
                                                   generate)
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.serving.kvcache.blocks import BlockPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# the decision table                                                          #
# --------------------------------------------------------------------------- #

def _pool(model, block_len, dtype, kv_quant=None):
    """The pool ``LMServingEngine`` builds for ``model`` (a class a kind of
    softmax layer, or one arena of latent rows), two blocks a class."""
    if model.latent_layers:
        classes = [dict(n_layers=len(model.latent_layers), n_heads=1,
                        head_dim=model.mla.row)]
    else:
        classes = [dict(n_layers=len(c.layers), n_heads=c.n_kv,
                        head_dim=c.k_dim, v_dim=c.v_dim, window=c.window)
                   for c in model.cache_classes]
    return BlockPool(classes=classes, block_len=block_len, num_blocks=2,
                     dtype=dtype, kv_quant=kv_quant,
                     latent=bool(model.latent_layers))


def _configured(name):
    """A benchmark configuration's model (unbuilt: the plan and the widths)
    and its pool's block length and dtype."""
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        c = json.load(f)
    if c["driver"] == "serve_lm":
        model = TransformerLM(
            vocab_size=c["vocab_size"], hidden_size=c["n_embd"],
            n_head=c["n_head"], n_layers=c["n_layer"],
            max_len=c["n_positions"])
    else:
        model = importlib.import_module(
            "benchmarks.drivers." + c["driver"]).build_model(c)
    return model, c["engine"]["block_len"], jnp.dtype(
        c["assumed"]["serve_dtype"])


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("name,on_a_tpu", [
    ("gpt2-xl", "gather"),                  # a head a query head, 64 lanes
    ("laguna-s-2.1", "paged_kernel"),       # grouped heads, windows
    ("solar-open2-250b", "paged_kernel"),   # grouped heads
    ("mimo-v2-flash", "paged_kernel"),      # sinks, two widths, two classes
    ("ling-3.0-flash-vl", "paged_kernel"),  # a latent pool
    ("glm-4.7-flash", "paged_kernel"),      # a latent pool, a drafter's layer
])
def test_auto_resolves_every_benchmark_configuration(monkeypatch, name,
                                                     on_a_tpu, backend):
    """What ``decode_attn="auto"`` resolves to in every cell: the walk on
    the CPU, and on a TPU the pool kind's kernel wherever the softmax layers'
    query heads share K/V heads (or the pool is latent) -- GPT-2 keeps the
    walk on every device."""
    model, block_len, dtype = _configured(name)
    pool = _pool(model, block_len, dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert decode_attention_path(model, pool) == (
        on_a_tpu if backend == "tpu" else "gather")
    assert decode_attention_path(model, pool, "gather") == "gather"


def _toy(kind, head_dim, n_kv):
    """A toy whose pool is of ``kind``: K/V heads shared by two query heads
    each under a window, or latent rows."""
    from bigdl_tpu.models.transformer import MLASpec, RopeSpec
    if kind == "latent":
        spec = LayerSpec(2, rope=RopeSpec(theta=1e4, rotary_dim=8),
                         mixer="mla")
        return TransformerLM(64, hidden_size=32, n_head=2, n_layers=2,
                             max_len=64, head_dim=16, pos_encoding="none",
                             bias=False, mla=MLASpec(24, 16, 8, 16),
                             layer_plan=[(2, (spec,))])
    return TransformerLM(64, hidden_size=32, n_head=2 * n_kv, n_layers=2,
                         max_len=64, head_dim=head_dim, pos_encoding="none",
                         bias=False, n_kv_head=n_kv,
                         layer_plan=[(2, (LayerSpec(2 * n_kv, window=16),))])


@pytest.mark.parametrize("kind,backend,block_len,head_dim,n_kv,resolved", [
    ("grouped", "tpu", 8, 128, 1, "paged_kernel"),
    ("grouped", "tpu", 4, 128, 1, "gather"),    # off float32's sublane tile
    ("grouped", "tpu", 8, 64, 2, "gather"),     # a head is half a lane tile
    ("grouped", "cpu", 8, 128, 1, "gather"),
    ("latent", "tpu", 8, None, None, "paged_kernel"),
    ("latent", "tpu", 4, None, None, "gather"),  # off the sublane tile
    ("latent", "cpu", 8, None, None, "gather"),
])
def test_auto_takes_the_kernel_where_the_chip_can(monkeypatch, kind, backend,
                                                  block_len, head_dim, n_kv,
                                                  resolved):
    """``auto`` by what the code can observe: the model's head grouping or
    the pool's kind, the backend and the compiled kernel's shape check;
    asked for by name, a geometry the chip's kernel cannot take is an
    error."""
    model = _toy(kind, head_dim, n_kv)
    pool = _pool(model, block_len, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert decode_attention_path(model, pool) == resolved
    if (backend, resolved) == ("tpu", "gather"):
        with pytest.raises(ValueError, match="multiple of 8|whole 128-lane"):
            decode_attention_path(model, pool, "paged_kernel")
    else:
        assert decode_attention_path(model, pool, "paged_kernel") == (
            "paged_kernel")


def _a_head_a_query_head(head_dim=128, window=None):
    return TransformerLM(64, hidden_size=2 * head_dim, n_head=2, n_layers=2,
                         max_len=64, head_dim=head_dim, pos_encoding="none",
                         layer_plan=[(2, (LayerSpec(2, window=window),))])


@pytest.mark.parametrize("window", [None, 16])
def test_auto_keeps_the_walk_for_a_head_a_query_head(monkeypatch, window):
    """One K/V head a query head, with or without windows, at a geometry the
    compiled kernel takes: ``auto`` is the walk on a TPU too (no cell says
    the kernel beats it there); asked for by name, the grouped kernel."""
    model = _a_head_a_query_head(window=window)
    pool = _pool(model, 8, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode_attention_path(model, pool) == "gather"
    assert decode_attention_path(model, pool, "paged_kernel") == "paged_kernel"


@pytest.mark.parametrize("requested", ["auto", "gather", "paged_kernel"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_an_int8_pool_is_gathered(monkeypatch, requested, backend):
    """The kernels read raw blocks: an int8 pool takes the walk, and the
    kernel asked for by name on it is an error."""
    model, block_len, _ = _configured("solar-open2-250b")
    pool = _pool(model, block_len, jnp.bfloat16, kv_quant="int8")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if requested == "paged_kernel":
        with pytest.raises(ValueError, match="requires decode_attn='gather'"):
            decode_attention_path(model, pool, requested)
    else:
        assert decode_attention_path(model, pool, requested) == "gather"


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_kernel_by_name_on_a_head_of_64_lanes(monkeypatch, backend):
    """GPT-2's head is half a lane tile: no compiled kernel takes it, so
    ``"paged_kernel"`` raises off the interpreter (which takes any size)."""
    model, block_len, dtype = _configured("gpt2-xl")
    pool = _pool(model, block_len, dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "tpu":
        with pytest.raises(ValueError, match="whole 128-lane"):
            decode_attention_path(model, pool, "paged_kernel")
    else:
        assert decode_attention_path(model, pool, "paged_kernel") == (
            "paged_kernel")


def test_unknown_request_is_refused():
    model = _a_head_a_query_head()
    with pytest.raises(ValueError, match="decode_attn must be"):
        decode_attention_path(model, _pool(model, 8, jnp.float32), "dense")


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
    """ACCEPTANCE: with the pool kind's kernel live at a group of ONE
    query head a K/V head (and radix sharing on), greedy AND sampled streams
    are bit-exact vs offline generate — the kernel changes memory traffic,
    never tokens."""
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


def test_engine_rejects_unknown_decode_attn():
    m = _lm()
    with pytest.raises(ValueError, match="decode_attn"):
        LMServingEngine(m, slots=1, cache_len=24, decode_attn="dense")
