"""The grouped-head decode kernel (``bigdl_tpu/ops/grouped_attention.py``)
against its oracle, the XLA walk over the live list
(``generate._paged_attention`` through ``_attend_by_owner``), and against a
float64 ``numpy`` softmax: the same arenas, the same blocks, the same new rows,
at toy geometry in the interpreter.  What the kernel changes is the order of the
float32 sums, so the three agree to 1e-5 of the output's size; and an engine
that serves through the kernel serves the logits and the streams the walk
serves.  The kernel compiled for the chip is ``tests/test_chip_compile.py``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from benchmarks.drivers import serve_laguna, serve_solar2
from benchmarks.tests import toy_laguna, toy_solar2
from benchmarks.tests.served import Served
from bigdl_tpu.models.transformer import generate as G
from bigdl_tpu.models.transformer import window_mask
from bigdl_tpu.ops import grouped_attention as ga
from bigdl_tpu.serving.kvcache.blocks import (SCRATCH_BLOCK, live_list,
                                              row_width)

REL = 1e-5
TOL = 2e-4          # served logits, the kernel's against the walk's
SEED = 5
FETCH = 4           # blocks a grid step fetches in these cases


def _case(lengths, *, group=8, n_kv=2, head_dim=16, block_len=16,
          table_width=12, layers=2, layer=1, dtype=jnp.bfloat16, seed=0):
    """K and V arenas of random rows, a chain of scattered blocks a slot as
    long as ``lengths`` says (0: an idle slot), ``group`` query heads a K/V
    head and the round's new rows -> what the walk and the kernel are handed."""
    slots, B, M = len(lengths), block_len, table_width
    rng = np.random.default_rng(seed)
    n = slots * M + 1
    w = row_width(n_kv, head_dim)
    arenas = []
    for _ in range(2):
        a = jnp.asarray(rng.standard_normal((layers, n, B, w)), dtype)
        arenas.append(a.at[..., n_kv * head_dim:].set(0))   # the lane padding
    q = jnp.asarray(2 * rng.standard_normal((slots, n_kv * group, 1, head_dim)),
                    jnp.float32)
    new = [jnp.asarray(rng.standard_normal((slots, n_kv, 1, head_dim)), dtype)
           for _ in range(2)]
    order = rng.permutation(np.arange(1, n))
    chains, tables = [], np.full((slots, M), SCRATCH_BLOCK, np.int32)
    for s, length in enumerate(lengths):
        held = -(-length // B)
        if held:
            chains.append((s, order[s * M:s * M + held]))
            tables[s, :held] = chains[-1][1]
    live = jnp.asarray(live_list(chains, slots * M, slots))
    pos = jnp.asarray([max(length - 1, 0) for length in lengths], jnp.int32)
    return dict(arenas=tuple(arenas), q=q, new=new, live=live, pos=pos, B=B,
                tables=jnp.asarray(tables), layer=layer, n_kv=n_kv,
                lengths=jnp.asarray(lengths, jnp.int32))


def _walk(c, window=None):
    """The oracle: the new rows written, then the list walked -> (o, arenas)."""
    _, owner, where = c["live"]
    slots, B, pos = c["pos"].shape[0], c["B"], c["pos"]
    # the block a slot's new row lands in (an idle slot's: the scratch block)
    blk = c["tables"][jnp.arange(slots), pos // B][:, None]
    k_pos = where[:, None] * B + jnp.arange(B)[None, :]
    q_pos = pos[jnp.minimum(owner, slots - 1)][:, None]
    mask = window_mask(q_pos, k_pos, window) & (owner < slots)[:, None, None]
    return G._paged_attention(c["q"], *c["new"], c["arenas"], c["layer"], blk,
                              (pos % B)[:, None], c["live"], mask)


def _float64(c, arenas, window=None):
    """A float64 softmax a slot and query head over the chain's own rows (the
    smoke's reference, which reads the same on the chip)."""
    q = np.asarray(c["q"], np.float64)[:, :, 0]
    k, v = (np.asarray(a[c["layer"]], np.float32) for a in arenas)
    return chip_smoke._float64_attention(
        q, k, v, np.asarray(c["tables"]), np.asarray(c["lengths"]), c["n_kv"],
        window, q.shape[-1])[:, :, None]


def _close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < REL * np.max(np.abs(want))


def _kernel(c, arenas, **kw):
    kw.setdefault("blocks_per_step", FETCH)
    return ga.grouped_decode_attention(c["q"], *arenas, c["tables"], c["lengths"],
                                       layer=c["layer"], n_kv_head=c["n_kv"],
                                       **kw)


CHAINS = {
    "one-block": [16, 3, 1],
    "exactly-a-step": [64, 64],                 # FETCH blocks, no more
    "a-step-and-a-block": [65, 80, 64],
    "ending-mid-block": [70, 41, 9, 119],
    "an-idle-slot-between": [50, 0, 130],
    "idle-slots-first-and-last": [0, 0, 77, 0],
    "scratch-padding-behind-a-short-chain": [5, 192],
    "every-entry-of-the-table": [192, 192],
}


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("name", sorted(CHAINS))
def test_the_kernel_reads_what_the_walk_reads(name, window):
    c = _case(CHAINS[name])
    want, arenas = _walk(c, window)
    got = _kernel(c, arenas, window=window)
    _close(got, want)
    _close(got, _float64(c, arenas, window))
    idle = np.asarray(c["lengths"]) == 0
    assert not np.asarray(got)[idle].any()                  # zeros, as the walk's
    assert not np.asarray(want)[idle].any()


@pytest.mark.parametrize("window", [None, 24, 64, 1000])
@pytest.mark.parametrize("group", [6, 8, 9])
def test_heads_a_group_and_windows(group, window):
    """Laguna's full layers, Solar's and Laguna's sliding layers' groups (the
    rows of a head are padded to whole sublane tiles inside), under no window,
    one inside a step, one of exactly a step and one longer than any chain."""
    c = _case([150, 0, 23, 64, 97], group=group, n_kv=3, seed=group)
    want, arenas = _walk(c, window)
    got = _kernel(c, arenas, window=window)
    _close(got, want)
    _close(got, _float64(c, arenas, window))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block_len", [16, 32])
def test_block_lengths_and_row_dtypes(block_len, dtype):
    """bfloat16 rows meet the operands in three pieces, float32 rows at the
    highest precision, as the walk's."""
    c = _case([block_len * 5 + 3, 1, 0, block_len * 2], block_len=block_len,
              table_width=6, dtype=jnp.dtype(dtype))
    want, arenas = _walk(c)
    got = _kernel(c, arenas, blocks_per_step=2)
    _close(got, want)
    _close(got, _float64(c, arenas))


@pytest.mark.parametrize("lengths,dtype,how", [
    ([24, 10, 15], "float32", "plain"),
    ([24, 13, 8], "bfloat16", "plain"),
    ([6, 1, 18], "float32", "plain"),       # ending mid-block, and position 0
    ([24, 10, 15], "float32", "3-D query"),
    ([24, 10, 15], "float32", "jit"),
])
def test_a_group_of_one_query_head(lengths, dtype, how):
    """ONE query head a K/V head (``H == n_kv``): what ``"paged_kernel"`` runs
    on a pool whose heads are not shared -- rows of both dtypes, chains ending
    mid-block and at position 0, the ``(S, H, D)`` query and under ``jit``."""
    c = _case(lengths, group=1, n_kv=2, head_dim=8, block_len=4,
              table_width=6, dtype=jnp.dtype(dtype), seed=len(how))
    want, arenas = _walk(c)
    if how == "3-D query":
        got = ga.grouped_decode_attention(
            c["q"][:, :, 0], *arenas, c["tables"], c["lengths"],
            layer=c["layer"], n_kv_head=2, blocks_per_step=FETCH)
        assert got.shape == (3, 2, 8)
        got = got[:, :, None]
    elif how == "jit":
        got = jax.jit(lambda *a: _kernel(c, a))(*arenas)
    else:
        got = _kernel(c, arenas)
    _close(got, want)
    _close(got, _float64(c, arenas))


@pytest.mark.parametrize("layers,layer", [(3, 0), (3, 2), (1, 0)])
def test_the_layer_is_an_operand_of_the_whole_arenas(layers, layer):
    """A traced layer index of arenas with several layers (the decode step's:
    the arenas ride the layer scan whole), and one layer's own arenas."""
    c = _case([37, 100], layers=layers, layer=layer, seed=3)
    want, arenas = _walk(c)

    def attend(layer, *arenas):
        return ga.grouped_decode_attention(
            c["q"], *arenas, c["tables"], c["lengths"], layer=layer,
            n_kv_head=2, blocks_per_step=FETCH)

    _close(jax.jit(attend)(jnp.int32(layer), *arenas), want)
    if layers == 1:
        _close(ga.grouped_decode_attention(
            c["q"][:, :, 0], arenas[0][0], arenas[1][0], c["tables"],
            c["lengths"], n_kv_head=2, blocks_per_step=FETCH), want[:, :, 0])


@pytest.mark.parametrize("window", [None, 50])
@pytest.mark.parametrize("fetch", [1, 5, 12, 64])
def test_steps_of_any_size(fetch, window):
    """A step of one block, one that does not divide the table, the whole table
    and more."""
    c = _case([7, 150, 33], seed=fetch)
    want, arenas = _walk(c, window)
    _close(_kernel(c, arenas, blocks_per_step=fetch, window=window), want)


def test_heads_by_the_rows_lanes_where_no_count_is_given():
    """A row without lane padding: ``n_kv_head`` is the row's lanes over D."""
    c = _case([40, 90], n_kv=8, group=2)
    assert c["arenas"][0].shape[-1] == 8 * 16
    want, arenas = _walk(c)
    _close(ga.grouped_decode_attention(
        c["q"], *arenas, c["tables"], c["lengths"], layer=1,
        blocks_per_step=FETCH), want)
    with pytest.raises(ValueError, match="do not divide"):
        ga.grouped_decode_attention(c["q"][:, :15], *arenas, c["tables"],
                                    c["lengths"], layer=1)


def test_the_kernel_only_reads_the_arenas():
    c = _case([20, 60])
    _, arenas = _walk(c)
    before = [np.asarray(a, np.float32) for a in arenas]
    _kernel(c, arenas).block_until_ready()
    for a, was in zip(arenas, before):
        assert (np.asarray(a, np.float32) == was).all()


@pytest.mark.parametrize("block_len,lanes,head_dim,dtype,says", [
    (8, 1024, 128, "bfloat16", "multiple of 16"),
    (4, 128, 128, "float32", "multiple of 8"),
    (16, 576, 64, "bfloat16", "whole 128-lane tiles"),
    (16, 1024, 64, "bfloat16", "head of whole 128-lane tiles"),
])
def test_a_geometry_the_compiled_kernel_cannot_take_raises(
        block_len, lanes, head_dim, dtype, says):
    with pytest.raises(ValueError, match=says):
        ga.check_grouped_kernel_shapes(block_len, lanes, head_dim,
                                       jnp.dtype(dtype))
    arena = jnp.zeros((1, 3, block_len, lanes), jnp.dtype(dtype))
    with pytest.raises(ValueError, match=says):
        ga.grouped_decode_attention(
            jnp.zeros((1, 2 * (lanes // head_dim), head_dim)), arena, arena,
            jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32), layer=0,
            interpret=False)
    ga.check_grouped_kernel_shapes(16, 1024, 128, jnp.bfloat16)  # the cells'
    ga.check_grouped_kernel_shapes(8, 256, 128, jnp.float32)


# -- an engine that serves through the kernel ---------------------------------
def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 96, size=(n,)).astype(np.int32)


MODELS = {"solar2": (serve_solar2, toy_solar2),     # G = 4 beside KDA layers
          "laguna": (serve_laguna, toy_laguna)}     # G = 2 and 3, windows of 8


def _engine(model, decode_attn):
    driver, toy = MODELS[model]
    c = toy.config()
    c["engine"] = dict(c["engine"], decode_attn=decode_attn)
    return driver.build_engine(c, SEED)


@pytest.fixture(scope="module", params=sorted(MODELS))
def engines(request):
    both = {impl: _engine(request.param, impl)
            for impl in ("paged_kernel", "gather")}
    yield both
    for eng in both.values():
        eng.close()


JOBS = {
    "one-stream-beside-idle-slots": [(11, 15)],
    "a-prompt-in-chunks": [(45, 8)],
    "six-requests-over-four-slots": [(5, 4), (30, 9), (12, 13), (8, 6), (17, 7),
                                     (3, 11)],
}


@pytest.mark.parametrize("name", sorted(JOBS))
def test_an_engine_serves_through_the_kernel_what_the_walk_serves(
        monkeypatch, engines, name):
    """``decode_attn="paged_kernel"`` on a pool of shared K/V heads: the same
    requests, teacher-forced, through both engines; the logits agree."""
    jobs = [(np.concatenate([[i], _ids(n, 20 + i)[1:]]).astype(np.int32),
             _ids(m, 40 + i)) for i, (n, m) in enumerate(JOBS[name])]
    rows = {}
    for impl, eng in engines.items():
        assert eng.stats()["decode_attn"] == impl
        with monkeypatch.context() as patch:
            served = Served(patch, eng)
            handles = [served.submit(p, f) for p, f in jobs]
            for who, stream in handles:
                stream.result(timeout=300)
            rows[impl] = [served.logits(who) for who, _ in handles]
            if len(jobs) > 4:
                assert {len(r) for r in served.rounds} & {1, 2, 3}  # idle slots
    for got, walked in zip(rows["paged_kernel"], rows["gather"]):
        assert got.shape == walked.shape
        assert np.max(np.abs(walked)) > 0.1
        assert np.max(np.abs(got - walked)) < TOL


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_greedy_and_sampled_streams_are_the_walks(engines, temperature):
    """The engine's own picks, on the device: the same prompts give the same
    tokens through the kernel and through the walk."""
    prompts = [_ids(n, 60 + n) + 1 for n in (6, 19, 33, 10, 27)]
    out = {}
    for impl, eng in engines.items():
        streams = [eng.submit(p, max_new_tokens=12, temperature=temperature,
                              rng=jax.random.PRNGKey(100 + i))
                   for i, p in enumerate(prompts)]
        out[impl] = [np.asarray(s.result(timeout=300)) for s in streams]
    for got, walked in zip(out["paged_kernel"], out["gather"]):
        assert len(got) > 10
        np.testing.assert_array_equal(got, walked)
