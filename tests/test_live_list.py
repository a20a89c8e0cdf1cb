"""The decode round reads the blocks its slots hold: a live list in place of
the slots x table-width gather (``serving.kvcache.blocks.live_list`` /
``list_chunk`` / ``table_list``, ``generate._paged_attention``,
``LMServingEngine._dispatch``).

Three levels, toy sizes, the CPU: the attention over a list against a dense
softmax written out here (grouped heads, a window, an int8 pool, the edges
of a list); the whole step with a short list against the same step with
every entry of every table (what a step gathered before there was a list),
logits to f32 round-off; the engine: one decode executable warmed, nothing
compiled after while the lists grow from one chunk to two, streams
token-exact with offline ``generate()``, and the two counters against a
schedule worked by hand.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import TransformerLM, generate as G
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.serving.kvcache import blocks as KB
from bigdl_tpu.serving.kvcache.blocks import BlockPool

B, M = 4, 6                 # block length, table width: 24 positions a slot


# -- the list itself --------------------------------------------------------------
def test_a_chunk_is_four_blocks_a_slot_and_sixteen_for_grouped_matmuls():
    assert KB.list_chunk(16) == 64          # gpt2-xl's cells: 16 x 64 entries
    assert KB.list_chunk(32, grouped=True) == 512   # laguna-s-2.1's: 32 x 160
    assert KB.list_chunk(1) == 4


@pytest.fixture
def chunks_of_16(monkeypatch):
    """Four slots: a chunk of 16 entries for either kind of attention, so that
    lists of 24 and 32 entries take two chunks."""
    monkeypatch.setattr(G, "list_chunk", lambda *a, **k: 16)


def test_a_list_names_block_owner_and_place_and_pads_with_nobodys_scratch():
    live = KB.live_list([(0, [7, 3]), (2, [5, 9, 4])], 8, slots=3)
    assert live.dtype == np.int32
    assert live.tolist() == [[7, 3, 5, 9, 4, 0, 0, 0],
                             [0, 0, 2, 2, 2, 3, 3, 3],
                             [0, 1, 0, 1, 2, 0, 0, 0]]
    tables = jnp.asarray([[7, 3, 0], [5, 9, 4]], jnp.int32)
    assert np.asarray(KB.table_list(tables)).tolist() == [
        [7, 3, 0, 5, 9, 4], [0, 0, 0, 1, 1, 1], [0, 1, 2, 0, 1, 2]]


# -- the attention over a list, against a dense softmax ----------------------------
def _pool(n_kv, d, kind, seed, layers=2, blocks=40):
    """A pool whose every block holds random rows (scratch too: what lands
    there is garbage, and nothing may read it); ``kind`` None (f32),
    "bfloat16" or "int8"."""
    quant = kind if kind == "int8" else None
    pool = BlockPool(n_layers=layers, n_heads=n_kv, head_dim=d, block_len=B,
                     num_blocks=blocks, dtype=jnp.dtype(kind or "float32"),
                     kv_quant=quant)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = (layers, blocks, B, n_kv, d)
    if quant:
        arenas = []
        for key in ks[:2]:
            q8, _ = G._kv_quantize_rows(jax.random.normal(key, rows))
            arenas.append(KB.pack_rows(q8, pool.shape[-1]))
        for key in ks[2:]:
            sc = jax.random.uniform(key, rows[:-1], minval=0.01, maxval=0.03)
            arenas.append(KB.pack_rows(sc, pool.scale_shape[-1]))
        return tuple(arenas)
    return tuple(KB.pack_rows(jax.random.normal(key, rows), pool.shape[-1]
                              ).astype(pool.dtype) for key in ks[:2])


def _dense(q, arenas, layer, tables, pos, window, n_kv, d):
    """Every slot's whole table, one softmax a (slot, head, row): numpy."""
    out = np.zeros(q.shape, np.float64)
    s_, h_, w_, _ = q.shape
    kv = []
    for a in arenas[:2]:
        a = np.asarray(a[layer], np.float64)[..., :n_kv * d]
        kv.append(a.reshape(a.shape[0], B, n_kv, d))
    if len(arenas) == 4:
        for i, a in enumerate(arenas[2:]):
            sc = np.asarray(a[layer], np.float64)[..., :B * n_kv]
            kv[i] = kv[i] * sc.reshape(-1, B, n_kv)[..., None]
    for s in range(s_):
        k = kv[0][tables[s]].reshape(-1, n_kv, d)       # (ctx, H_kv, D)
        v = kv[1][tables[s]].reshape(-1, n_kv, d)
        for w in range(w_):
            at = pos[s] + w
            see = np.arange(k.shape[0]) <= at
            if window:
                see &= np.arange(k.shape[0]) > at - window
            for h in range(h_):
                kh = h // (h_ // n_kv)
                sc = k[:, kh] @ np.asarray(q[s, h, w], np.float64) / np.sqrt(d)
                sc = np.where(see, sc, -np.inf)
                p = np.exp(sc - sc.max())
                out[s, h, w] = (p / p.sum()) @ v[:, kh]
    return out


#: (query heads, K/V heads, head size, window, pool): GPT-2's shape in small (a
#: row of 4 x 16 lanes padded to 128: one query vector a K/V head, every slot
#: against every listed position), grouped heads under a window shorter than
#: the chains and under none (several: grouped matmuls), and each again over
#: the pools whose rows meet the queries in bfloat16 pieces
SHAPES = {
    "gpt2": (4, 4, 16, None, None),
    "gpt2-bf16": (4, 4, 16, None, "bfloat16"),
    "grouped-sliding": (6, 2, 16, 8, None),
    "grouped-full": (4, 2, 16, None, None),
    "grouped-bf16": (6, 2, 16, 8, "bfloat16"),
    "int8": (4, 4, 16, None, "int8"),
    "grouped-int8": (6, 2, 16, None, "int8"),
}

#: slot -> position being written (None: idle).  Four slots of 24 positions
EDGES = {
    "mixed": [13, None, 5, 22],
    "idle-first-and-last": [None, 9, 17, None],
    "fresh-block": [8, 4, None, 12],            # position 0 of blocks 2, 1, 3
    "one-token-chains": [0, None, None, 1],
    "every-slot-at-cache-len": [23, 23, 23, 23],
}


def _tables(pos, seed=0):
    """Distinct blocks for every position up to the write position."""
    ids = np.random.RandomState(seed).permutation(np.arange(1, 40))
    tables, chains, at = np.zeros((len(pos), M), np.int32), [], 0
    for s, p in enumerate(pos):
        if p is None:
            continue
        n = p // B + 1
        tables[s, :n] = ids[at:at + n]
        chains.append((s, tables[s, :n].tolist()))
        at += n
    return tables, chains


@pytest.mark.parametrize("edge", sorted(EDGES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_attention_over_a_list_is_the_dense_softmax(shape, edge, chunks_of_16):
    h, n_kv, d, window, kind = SHAPES[shape]
    quant = kind if kind == "int8" else None
    where = EDGES[edge]
    s = len(where)
    pos = np.asarray([p or 0 for p in where], np.int32)
    tables, chains = _tables(where)
    arenas = _pool(n_kv, d, kind, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (s, h, 1, d))
    k = jax.random.normal(ks[1], (s, n_kv, 1, d))
    v = jax.random.normal(ks[2], (s, n_kv, 1, d))
    blk = jnp.asarray(tables[np.arange(s), pos // B])[:, None]
    off = jnp.asarray(pos % B)[:, None]
    n_live = sum(len(c) for _, c in chains)
    model = type("M", (), {"plan": ((1, (type("S", (), {"window": window}),)),)})
    got = {}
    # a chunk is 16 entries here.  Exactly the list's length, one entry over
    # it, the engine's length (whole tables: 24, not a multiple of a chunk)
    # and every table entry, scratch padding and all
    for name, live in [
            ("exact", KB.live_list(chains, n_live, s)),
            ("padded", KB.live_list(chains, n_live + 1, s)),
            ("top", KB.live_list(chains, s * M, s)),
            ("tables", np.asarray(KB.table_list(jnp.asarray(tables))))]:
        live = jnp.asarray(live)
        mask = G._list_masks(model, live, jnp.asarray(pos)[:, None], B)[window]
        o, new = jax.jit(G._paged_attention, static_argnums=(4,))(
            q, k, v, arenas, 1, blk, off, live, mask)
        got[name] = np.asarray(o)
    want = _dense(np.asarray(q), new, 1, tables, pos, window, n_kv, d)
    live_slots = [i for i, p in enumerate(where) if p is not None]
    for name, o in got.items():
        assert np.isfinite(o).all(), name           # idle slots: garbage, finite
        err = np.max(np.abs(o[live_slots] - want[live_slots]))
        assert err < (2e-5 if quant is None else 2e-4), (name, err)
    # the new rows landed where the tables say, layer 1 only
    assert np.array_equal(np.asarray(new[0][0]), np.asarray(arenas[0][0]))
    row = np.asarray(new[0][1, tables[live_slots[0], pos[live_slots[0]] // B],
                            pos[live_slots[0]] % B])
    if kind is None:
        np.testing.assert_allclose(row[:n_kv * d],
                                   np.asarray(k[live_slots[0], :, 0]).ravel())


# -- the whole step: a short list against every entry of every table ---------------
def _toy_gpt2():
    return TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=3,
                         max_len=24).build(seed=11).evaluate()


def _toy_laguna():
    from benchmarks.drivers import serve_laguna as D
    from benchmarks.tests import toy_laguna
    c = toy_laguna.config()
    model = D.build_model(c)
    model.params = D.program_params(model, 5, c, "float32")
    model.buffers = {}
    return model.evaluate()


def _class_arenas(model, kind, seed):
    """A pool's arenas for ``model``: a CLASS of blocks a kind of softmax
    layer (``model.cache_classes``), arenas of its own layers, side by side."""
    return sum((_pool(c.n_kv, c.k_dim, kind, seed=seed + i, layers=len(c.layers))
                for i, c in enumerate(model.cache_classes)), ())


def _class_lists(model, full, chains, where):
    """A round's live list for ``model``: ``full`` (a list of whole chains, or
    whole tables) for a class without a window; a class with one lists the
    blocks its window touches, side by side with the full class's entries."""
    slots = len(where)
    parts = []
    for c in model.cache_classes:
        if c.window is None:
            parts.append(np.asarray(full))
            continue
        first = {s: max(0, p - c.window + 1) // B
                 for s, p in enumerate(where) if p is not None}
        parts.append(KB.live_list(
            [(s, blocks[first[s]:], first[s]) for s, blocks in chains],
            KB.class_entries(slots, M, c.window, B), slots))
    return jnp.asarray(np.concatenate(parts, axis=1))


@pytest.mark.parametrize("case", ["gpt2", "laguna", "int8"])
def test_step_with_a_short_list_is_the_step_with_whole_tables(case,
                                                             chunks_of_16):
    """Toy GPT-2, toy Laguna (2 K/V heads under 6 and 4 query heads, sliding
    layers of window 8 under chains of up to 23 positions, a full layer, routed
    experts) and an int8 pool: the same tokens, logits to f32 round-off, the
    same rows written, whether the step reads its 12 listed blocks (one chunk
    of 16) or the 24 entries of its four tables (two chunks)."""
    model = _toy_laguna() if case == "laguna" else _toy_gpt2()
    where = [13, None, 5, 22]
    pos = jnp.asarray([p or 0 for p in where], jnp.int32)
    tables, chains = _tables(where, seed=3)
    arenas = _class_arenas(model, "int8" if case == "int8" else None, seed=4)
    token = jnp.asarray([3, 0, 17, 8], jnp.int32)

    def step(live):
        return G._decode_step_paged(model, model.params, token, pos, live,
                                    *arenas, table_width=M)

    def listed(full):
        return _class_lists(model, full, chains, where)

    n_live = sum(len(c) for _, c in chains)
    short = jax.jit(step)(listed(KB.live_list(chains, 24, 4)))
    whole = jax.jit(step)(listed(KB.table_list(jnp.asarray(tables))))
    assert n_live == 12 and len(short) == len(whole)
    live_slots = [0, 2, 3]
    a, b = np.asarray(short[0])[live_slots], np.asarray(whole[0])[live_slots]
    assert np.array_equal(a.argmax(-1), b.argmax(-1))
    assert np.max(np.abs(a - b)) < 1e-5 * max(1.0, np.max(np.abs(b)))
    if case == "laguna":        # the routed layers' integers: idle slot unrouted
        assert np.array_equal(np.asarray(short[1]), np.asarray(whole[1]))
    # the arenas agree everywhere but the scratch block, where a whole table's
    # idle slot and a list's write their garbage alike
    for x, y in zip(short[-len(arenas):], whole[-len(arenas):]):
        np.testing.assert_allclose(np.asarray(x)[:, 1:], np.asarray(y)[:, 1:],
                                   rtol=1e-6, atol=1e-6)


# -- the engine ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """Two slots, blocks of 4, tables 12 wide: a chunk of 8 entries in a list
    of 24.  Three requests whose chains grow from one chunk to two; the
    third waits for a slot."""
    m = TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=2,
                      max_len=48).build(seed=7).evaluate()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 62, size=n) for n in (5, 9, 14)]
    new = [20, 12, 6]
    offline = [np.asarray(G.generate(m, m.params, p[None], k))[0, len(p):].tolist()
               for p, k in zip(prompts, new)]
    eng = LMServingEngine(m, slots=2, block_len=4, cache_len=48,
                          prefill_buckets=(8, 16), max_new_tokens=20,
                          enable_prefix_cache=False)
    eng.warmup()
    after_warmup = eng._decode_exec
    lower = eng._decode_jit
    eng._decode_jit = None          # a compile after warm-up would raise
    streams = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, new)]
    got = [list(map(int, s.result(timeout=300)))[len(p):]
           for s, p in zip(streams, prompts)]
    stats = eng.stats()["metrics"]
    eng._decode_jit = lower
    yield dict(engine=eng, offline=offline, got=got, stats=stats,
               after_warmup=after_warmup)
    eng.close()


def test_warmup_compiles_the_one_step_and_growing_lists_compile_nothing(
        served):
    """``warmup()`` leaves ONE decode executable; rounds whose lists fill one
    chunk and then two run on it (the engine's jitted step was taken away
    after warm-up: a compile would have raised), token-exact with offline
    ``generate()``."""
    eng = served["engine"]
    assert served["after_warmup"] is not None
    assert eng._decode_exec is served["after_warmup"]
    assert served["got"] == served["offline"]


def test_counters_are_the_schedule_worked_by_hand(served):
    """Requests of 5 + 20, 9 + 12 and 14 + 6 tokens on two slots: a round in
    which a slot writes position p reads p // 4 + 1 of its blocks.  The first
    token comes from the prefill, so a request of n new tokens decodes n - 1
    rounds, writing positions len(prompt) .. len(prompt) + n - 2; the third
    request takes the second's slot when that finishes."""
    def rounds(prompt_len, n_new):
        return [(prompt_len + i) // 4 + 1 for i in range(n_new - 1)]

    first, second, third = rounds(5, 20), rounds(9, 12), rounds(14, 6)
    # slot 0 decodes 19 rounds; slot 1 11 rounds of the second request, then 5
    # of the third: rounds 0..10 hold (first, second), 11..15 (first, third),
    # 16..18 first alone
    live = [first[i] + second[i] for i in range(11)]
    live += [first[11 + i] + third[i] for i in range(5)]
    live += first[16:]
    stats = served["stats"]
    assert stats["decode_steps"] == len(live) == 19
    assert stats["live_blocks"] == sum(live)
    chunks = lambda n: -(-n // 8) * 8                            # noqa: E731
    assert stats["gathered_blocks"] == sum(chunks(n) for n in live)
    assert {chunks(n) for n in live} == {8, 16}     # one chunk, then two
    from bigdl_tpu.obs import get_registry
    snap = get_registry().snapshot()
    assert snap["serving/lm/live_blocks"]["value"] == stats["live_blocks"]
    assert snap["serving/lm/gathered_blocks"]["value"] == stats["gathered_blocks"]


def test_decode_step_span_carries_the_list_it_read():
    from bigdl_tpu.obs import get_tracer
    m = TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=1,
                      max_len=32).build(seed=2).evaluate()
    tracer = get_tracer()
    was = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        with LMServingEngine(m, slots=2, block_len=4, cache_len=32,
                             prefill_buckets=(8,), max_new_tokens=4) as eng:
            eng.submit(np.arange(1, 7), max_new_tokens=4).result(timeout=300)
        steps = [e for e in tracer.events() if e["name"] == "lm/decode_step"]
    finally:
        tracer.enabled = was
        tracer.clear()
    # prompt of 6, three decode rounds writing positions 6, 7, 8; two slots:
    # a chunk of 8
    assert [e["args"]["live_blocks"] for e in steps[-3:]] == [2, 2, 3]
    assert [e["args"]["gather_blocks"] for e in steps[-3:]] == [8, 8, 8]
