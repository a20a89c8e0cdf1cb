"""The pool with a CLASS of blocks a kind of softmax layer (``serving.kvcache.
blocks``), at MiMo-V2-Flash's toy size: the allocator's promises as properties,
what a windowed class refuses, configurations of one kind on today's arenas,
the decode kernel at two widths with a sink against the walk, and the four
controls of the comparison that decides ``correct``
(``benchmarks/tests/toy_mimo_v2.CONTROLS``), each refused.  Beside
``tests/test_mimo_v2.py``: the program against its plain reference (``benchmarks/harness/
reference_mimo_v2.py``), logits not tokens, through the pool with a CLASS of
blocks a kind of softmax layer -- two K/V head counts, keys wider than values,
a window that lets go of what lies behind it, a sink -- and the allocator's
own promises as properties.

Tolerances: the toy serves float32, so program and reference differ by the
order of float32 sums alone (1e-5 of logits of size 1-10; ``TOL``); every
control moves the logits a thousand times that or more."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_mimo_v2 as D
from benchmarks.harness import reference_mimo_v2 as R
from benchmarks.tests import toy_mimo_v2
from benchmarks.tests.served import Served
from benchmarks.tests.toy_mimo_v2 import config as toy
from bigdl_tpu.parallel import expert as E
from bigdl_tpu.serving.kvcache import blocks as KB
from bigdl_tpu.serving.kvcache.blocks import (BlockPool, PoolExhausted,
                                              SCRATCH_BLOCK)

SEED, TOL = 5, 3e-5
WINDOW, B = 8, 4


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 96, size=(n,)).astype(np.int32)


def _model(c):
    model = D.build_model(c)
    model.params = D.program_params(model, SEED, c, c["assumed"]["serve_dtype"])
    model.buffers = {}
    return model.evaluate()


@pytest.fixture(scope="module")
def reference_weights():
    return R.make_weights(SEED, toy(), "float32")


@pytest.fixture(scope="module")
def engine():
    eng = D.build_engine(toy(), SEED)
    yield eng
    eng.close()


def _serve(monkeypatch, engine, jobs):
    """Teacher-forced requests through the engine -> their logits rows."""
    with monkeypatch.context() as patch:
        served = Served(patch, engine)
        handles = [served.submit(p, f) for p, f in jobs]
        for _, stream in handles:
            stream.result(timeout=300)
        return [served.logits(who) for who, _ in handles]


def _want(weights, c, prompt, forced):
    ids = np.concatenate([prompt, forced])
    t = len(prompt)
    return np.asarray(R.forward(weights, c, ids))[t - 1:t - 1 + len(forced)]


# -- the allocator ---------------------------------------------------------------
def _pool(blocks=(40, 14)):
    return BlockPool(classes=[
        dict(n_layers=2, n_heads=1, head_dim=24, v_dim=16, num_blocks=blocks[0]),
        dict(n_layers=5, n_heads=2, head_dim=24, v_dim=16, window=WINDOW,
             num_blocks=blocks[1])], block_len=B)


def test_a_class_has_arenas_a_free_list_and_refcounts_of_its_own():
    pool = _pool()
    full, sliding = pool.classes
    assert full.shape == (2, 40, B, 128) and full.v_shape == (2, 40, B, 128)
    assert sliding.shape == (5, 14, B, 128)         # 2 x 24 = 48 -> one tile
    assert [a.shape for a in pool.arenas] == [
        full.shape, full.v_shape, sliding.shape, sliding.v_shape]
    chain = pool.alloc(3)
    assert chain == [(1, 0), (2, 0), (3, 0)]        # the windowed class: later
    assert pool.free_in(0) == 36 and pool.free_in(1) == 13
    marks = [0, 0]
    assert pool.advance(chain, marks, 0, 9) == (0, 3)
    assert [e[1] for e in chain] == [1, 2, 3] and pool.free_in(1) == 10
    pool.release(chain)
    assert pool.free_in(0) == 39 and pool.free_in(1) == 13
    assert pool.stats()["classes"][1]["window"] == WINDOW


@pytest.mark.parametrize("seed", range(4))
def test_the_allocators_promises_over_random_schedules(seed):
    """Random admissions, advances (decode rounds and prefill chunks) and
    finishes: a sequence holds no block of the windowed class wholly behind
    its window and at most ceil(window / block_len) + 1 plus its chunk's; what
    it let go is free for another at once, and no two live holders write the
    same block; exhaustion names the class."""
    rng = np.random.RandomState(seed)
    pool = _pool((400, 24))
    promise = KB.window_blocks(WINDOW, B)
    live = []                                   # [chain, marks, pos, total]
    for _ in range(300):
        move = rng.randint(4)
        if move == 0 and len(live) < 4:
            total = int(rng.randint(8, 90))
            live.append([pool.alloc(pool.blocks_for(total)), [0, 0], 0, total])
        elif move == 1 and live:
            seq = live.pop(rng.randint(len(live)))
            pool.release(seq[0])
        elif live:
            seq = live[rng.randint(len(live))]
            chain, marks, pos, total = seq
            step = int(min(rng.choice([1, 1, 1, 8, 13]), total - pos))
            if step <= 0:
                continue
            try:
                pool.advance(chain, marks, pos, pos + step)
            except PoolExhausted as e:
                assert "class 1" in str(e)
                continue
            seq[2] = pos = pos + step
            pool.advance(chain, marks, pos, pos)        # after the chunk
            held = [i for i, e in enumerate(chain) if e[1] != SCRATCH_BLOCK]
            first = max(0, pos - WINDOW + 1) // B
            assert all(i >= first for i in held)
            assert len(held) <= promise + 1
            assert all(e[0] != SCRATCH_BLOCK for e in chain)
        # a block belongs to one live holder, and the books balance
        mine = [e[1] for s in live for e in s[0] if e[1] != SCRATCH_BLOCK]
        assert len(mine) == len(set(mine))
        assert pool.free_in(1) == pool.classes[1].capacity - len(mine)
    for seq in live:
        pool.release(seq[0])
    assert pool.free_in(0) == 399 and pool.free_in(1) == 23


def test_a_request_that_a_class_can_never_hold_is_refused_and_says_which():
    from bigdl_tpu.serving.kvcache import RequestExceedsPool
    eng = D.build_engine(toy(), SEED, num_blocks=[120, 5], enable_prefix_cache=False)
    try:
        with pytest.raises(RequestExceedsPool, match="class 1, window 8"):
            eng.submit(_ids(40) + 1, max_new_tokens=4)
        # a short one fits (its whole chain is 2 blocks)
        assert len(eng.submit(_ids(5) + 1, max_new_tokens=3).result(timeout=60)) == 8
    finally:
        eng.close()


def test_the_windowed_class_refuses_what_it_cannot_do_yet():
    from bigdl_tpu.serving import lm_engine
    model = D.build_model(toy())
    for asked, what in ((dict(migrate=object()), "migrate"),
                        (dict(kvtier=object()), "kvtier"),
                        (dict(spec=type("S", (), {"tree": True, "k": 2,
                                                   "draft": object()})()),
                         "tree verify"),
                        (dict(adopt=True), "adopt")):
        with pytest.raises(ValueError, match=what):
            lm_engine.refuse_unsupported(model, **asked)


# -- configurations of one kind build today's pool ---------------------------------
@pytest.mark.parametrize("name", ["gpt2", "solar2"])
def test_a_model_of_one_kind_is_a_pool_of_one_class(name):
    """GPT-2's and Solar's toys: one class, the arenas of the shapes a pool
    built from (layers, K/V heads, head size) has, a chain entry a block id."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    if name == "gpt2":
        m = TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=3,
                          max_len=48).build(seed=11).evaluate()
        eng = LMServingEngine(m, slots=2, block_len=4, cache_len=48,
                              prefill_buckets=(8, 16), num_blocks=30)
        layers, heads, d = 3, 4, 8
    else:
        from benchmarks.drivers import serve_solar2
        from benchmarks.tests import toy_solar2
        c = toy_solar2.config()
        eng = serve_solar2.build_engine(c, SEED)
        m = eng.model
        layers, heads, d = len(m.kv_layers), m.n_kv_head, m.head_dim
    try:
        assert len(m.cache_classes) == len(eng.pool.classes) == 1
        want = BlockPool(n_layers=layers, n_heads=heads, head_dim=d,
                         block_len=eng.block_len, num_blocks=eng.pool.num_blocks,
                         dtype=eng.pool.dtype)
        assert [a.shape for a in eng.pool.arenas] == [a.shape for a in want.arenas]
        assert eng.pool.shape == want.shape and len(eng.pool.arenas) == 2
        assert eng.pool.alloc(2) == want.alloc(2) == [1, 2]
        assert not eng.pool.windowed and eng._live_entries == [
            eng.slots * eng.table_width]
    finally:
        eng.close()


# -- the kernel -------------------------------------------------------------------
@pytest.mark.parametrize("n_kv,window", [(2, 24), (1, None)])
def test_the_kernel_at_two_widths_with_a_sink_is_the_walk(n_kv, window):
    """``ops.grouped_attention`` interpreted, keys of 24 and values of 16
    lanes, a sink a query row, against the live list's walk."""
    from bigdl_tpu.models.transformer import generate as G, window_mask
    from bigdl_tpu.ops.grouped_attention import grouped_decode_attention
    slots, heads, dk, dv, blk, m = 3, 4, 24, 16, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    pool = BlockPool(classes=[dict(n_layers=2, n_heads=n_kv, head_dim=dk,
                                   v_dim=dv, num_blocks=40)], block_len=blk)
    ka = jax.random.normal(ks[0], pool.classes[0].shape)
    va = jax.random.normal(ks[1], pool.classes[0].v_shape)
    q = jax.random.normal(ks[2], (slots, heads, 1, dk))
    sink = jax.random.normal(ks[3], (heads,)) + 1.0
    pos = np.array([37, 0, 9])
    lengths = np.array([38, 0, 10], np.int32)
    rng = np.random.RandomState(1)
    tables = np.zeros((slots, m), np.int32)
    chains = []
    for s, n in enumerate(lengths):
        held = rng.choice(np.arange(1, 40), size=-(-int(n) // blk), replace=False)
        tables[s, :len(held)] = held
        if n:
            chains.append((s, held))
    live = jnp.asarray(KB.live_list(chains, slots * m, slots))
    k_pos = live[2][:, None] * blk + jnp.arange(blk)[None, :]
    q_pos = jnp.asarray(pos)[jnp.minimum(live[1], slots - 1)][:, None]
    mask = window_mask(q_pos, k_pos, window) & (live[1] < slots)[:, None, None]
    new_k = jnp.zeros((slots, n_kv, 1, dk))
    new_v = jnp.zeros((slots, n_kv, 1, dv))
    # (the walk writes its new rows first: into the scratch block here)
    want, _ = G._paged_attention(q, new_k, new_v, (ka, va), 1,
                                 jnp.zeros((slots, 1), jnp.int32),
                                 jnp.zeros((slots, 1), jnp.int32), live, mask,
                                 sink=sink)
    got = grouped_decode_attention(q, ka, va, jnp.asarray(tables),
                                   jnp.asarray(lengths), layer=1, n_kv_head=n_kv,
                                   window=window, sink=sink, v_dim=dv,
                                   blocks_per_step=4, interpret=True)
    assert got.shape == (slots, heads, 1, dv)
    live_slots = np.asarray([0, 2])
    assert float(jnp.max(jnp.abs(got[live_slots] - want[live_slots]))) < 2e-5
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0        # idle: zeros


# -- the controls -----------------------------------------------------------------
@pytest.mark.parametrize("control", sorted(toy_mimo_v2.CONTROLS))
def test_every_control_is_refused(monkeypatch, reference_weights, control):
    """The sink left out, the window's release one block early, the values
    unscaled, K/V rounded to int8: each moves the served logits far outside
    the tolerance."""
    c, prompt, forced = toy(), _ids(19, 11), _ids(14, 12)
    with toy_mimo_v2.CONTROLS[control]():
        eng = D.build_engine(c, SEED)
        try:
            got, = _serve(monkeypatch, eng, [(prompt, forced)])
        finally:
            eng.close()
    gap = np.max(np.abs(got - _want(reference_weights, c, prompt, forced)))
    assert gap > 100 * TOL, (control, gap)
