"""Speculation 2.0: adaptive token-tree verification + prompt lookup.

Tier-1 coverage of tree mode on the spec engine: bit-exactness of
greedy AND sampled tree-speculative streams vs offline ``generate``
(including an int8 target with radix sharing on), the shape-ladder
machinery and the pure tree acceptance walk, the bounded-executables
contract (exactly one donated verify per ladder rung), deterministic
acceptance-collapse demotion and re-probe under tree budgets, the
``serving.verify`` fault site on tree rounds, the zero-model
``NgramDrafter`` (determinism, vocab guard, engine exactness at zero
drafter steps), and tree metrics exposure.
"""
import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.models.transformer.generate import generate
from bigdl_tpu.obs import get_registry
from bigdl_tpu.serving import LMServingEngine, SpecConfig
from bigdl_tpu.serving.spec import (NgramDrafter, TreeShape,
                                    default_tree_shapes, tree_accept_walk)


def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=64, seed=0):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers,
                         max_len=max_len).build(seed=seed)


def _ref(model, prompt, max_new, temperature=0.0, seed=None):
    kw = dict(temperature=temperature)
    if seed is not None:
        import jax
        kw["rng"] = jax.random.PRNGKey(seed)
    return np.asarray(generate(model, model.params,
                               np.asarray(prompt)[None].astype(np.int32),
                               max_new, **kw))[0]


@pytest.fixture(scope="module")
def lm_model():
    return _lm()


@pytest.fixture(scope="module")
def tree_engine(lm_model):
    """One shared tree-mode engine for the read-only fast tests (every
    engine compiles prefill + one verify per ladder rung + the drafter
    programs, so sharing keeps tier-1 inside budget)."""
    eng = LMServingEngine(lm_model, slots=4, cache_len=48, block_len=4,
                          max_new_tokens=12, prefill_buckets=(8, 16),
                          spec=SpecConfig(k=3, tree=True,
                                          promote_above=0.5))
    eng.warmup()
    yield eng
    eng.close()


# --------------------------------------------------------------------------- #
# shape machinery + pure walk                                                 #
# --------------------------------------------------------------------------- #

def test_tree_shape_machinery():
    shapes = default_tree_shapes(3)
    assert [s.width for s in shapes] == [2, 3, 4, 7]
    assert [s.is_chain for s in shapes] == [True, True, True, False]
    # nested-prefix ladder: every rung is a prefix of the next
    for lo, hi in zip(shapes, shapes[1:]):
        assert hi.parents[:lo.width] == lo.parents
    top = shapes[-1]
    assert top.spine == 3 and top.max_depth == 3
    assert top.alt_counts == (1, 1, 1)
    assert top.alt_rank == {4: 0, 5: 0, 6: 0}
    # the ancestor matrix of a chain is lower-triangular
    assert np.array_equal(shapes[2].anc, np.tril(np.ones((4, 4), bool)))
    with pytest.raises(ValueError, match="earlier"):
        TreeShape([-1, 1])             # forward parent
    with pytest.raises(ValueError, match="leaves"):
        TreeShape([-1, 0, 0, 2])       # alternate with a child
    with pytest.raises(ValueError, match="spine"):
        TreeShape([-1, 0, 1, 1, 2])    # alternate off the spine tip


def test_tree_spec_config_validation():
    with pytest.raises(ValueError, match="replay-only"):
        SpecConfig(k=2, tree=True, sampling="rejection")
    with pytest.raises(ValueError, match="q distribution"):
        SpecConfig(k=2, drafter_compute="ngram", sampling="rejection")
    with pytest.raises(ValueError, match="tree_shapes requires"):
        SpecConfig(k=2, tree_shapes=[[-1, 0]])
    cfg = SpecConfig(k=3, tree=True)
    # default init rung: the deepest chain (linear-k until the EMA says
    # otherwise)
    assert cfg.shapes[cfg.init_rung].is_chain
    assert cfg.shapes[cfg.init_rung].spine == 3
    d = cfg.describe()
    assert d["tree"] and d["tree_widths"] == [2, 3, 4, 7]


def test_tree_accept_walk_unit():
    """Root emits the alternate's token -> the walk leaves the spine,
    emits one bonus from the alternate row, and stops (alternates are
    leaves)."""
    shape = TreeShape([-1, 0, 1, 0])   # spine 0-1-2, alternate 3 off root
    v = 8
    rows = np.full((4, v), -10.0, np.float32)
    rows[0, 6] = rows[3, 2] = 10.0     # root picks 6 == node 3's token
    rows[1, 1] = rows[2, 1] = 10.0
    emitted, path = tree_accept_walk(shape, [9, 4, 5, 6], rows, 0.0, None)
    assert emitted == [6, 2] and path == [0, 3]
    # spine match: full chain plus bonus from the deepest node
    rows2 = np.full((4, v), -10.0, np.float32)
    rows2[0, 4] = rows2[1, 5] = rows2[2, 7] = 10.0
    emitted, path = tree_accept_walk(shape, [9, 4, 5, 6], rows2, 0.0, None)
    assert emitted == [4, 5, 7] and path == [0, 1, 2]
    # n_cand truncation hides the alternate
    emitted, path = tree_accept_walk(shape, [9, 4, 5, 6], rows, 0.0, None,
                                     n_cand=3)
    assert emitted == [6] and path == [0]


# --------------------------------------------------------------------------- #
# bit-exactness vs offline generate                                           #
# --------------------------------------------------------------------------- #

def test_tree_greedy_exact_vs_offline(tree_engine, lm_model):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 32, size=n).astype(np.int32)
               for n in (5, 9, 14)]
    streams = [tree_engine.submit(p, max_new_tokens=12) for p in prompts]
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(s.result(timeout=60),
                                      _ref(lm_model, p, 12))
    spec = tree_engine.stats()["spec"]
    assert spec["drafted"] > 0
    assert spec["tree_rounds"] > 0
    assert spec["acceptance_rate"] > 0.0


def test_tree_sampled_exact_vs_offline(tree_engine, lm_model):
    rng = np.random.default_rng(1)
    cases = [(rng.integers(1, 32, size=n).astype(np.int32), t, s)
             for (n, t, s) in ((6, 0.7, 3), (11, 1.3, 4))]
    streams = [tree_engine.submit(p, max_new_tokens=12, temperature=t,
                                  rng=s) for p, t, s in cases]
    for (p, t, s), stm in zip(cases, streams):
        np.testing.assert_array_equal(
            stm.result(timeout=60), _ref(lm_model, p, 12, t, s))


def test_tree_int8_target_with_radix_sharing(lm_model):
    """The hardest combination again, now under tree verify: int8
    target (quantized KV write path in the tree kernel), radix prefix
    sharing on, greedy + sampled — still the offline trajectory."""
    qlm = lm_model.quantize("int8")
    eng = LMServingEngine(qlm, slots=4, cache_len=48, block_len=4,
                          max_new_tokens=8, prefill_buckets=(8, 16),
                          spec=SpecConfig(k=3, tree=True))
    eng.warmup()
    try:
        rng = np.random.default_rng(2)
        base = rng.integers(1, 32, size=8).astype(np.int32)
        cases = [(base, 0.0, None), (base.copy(), 0.7, 3),
                 (np.concatenate([base, [5, 7]]).astype(np.int32),
                  0.9, 4)]
        streams = [eng.submit(p, max_new_tokens=8, temperature=t,
                              rng=s) for p, t, s in cases]
        for (p, t, s), stm in zip(cases, streams):
            np.testing.assert_array_equal(
                stm.result(timeout=60), _ref(qlm, p, 8, t, s))
        assert eng.radix.hit_rate() > 0.0
        assert eng.stats()["spec"]["tree_rounds"] > 0
    finally:
        eng.close()


@pytest.mark.parametrize("quant", [None, "int8"])
def test_tree_commit_moves_rows_through_the_pool_layout(quant):
    """``_tree_commit_paged`` in the block-contiguous layout: an accepted
    off-spine node's row moves from its store offset to its position
    offset (across a block boundary too), data and, quantized, its
    scales; identity rows and every other row stay as they were."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer.generate import _tree_commit_paged
    from bigdl_tpu.serving.kvcache import BlockPool
    from bigdl_tpu.serving.kvcache.blocks import read_chain, write_rows
    L, H, D, B = 2, 5, 64, 4                 # H * D = 320: lane-padded rows
    pool = BlockPool(n_layers=L, n_heads=H, head_dim=D, block_len=B,
                     num_blocks=8, dtype=jnp.float32, kv_quant=quant)
    tables = jnp.asarray([[3, 5, 1], [2, 6, 4]], jnp.int32)
    idx = tables.reshape(-1)
    rng = np.random.default_rng(0)
    rows = rng.integers(-90, 90, (L, 6, B, H, D)).astype(pool.dtype)
    every = slice(None)
    arenas = [write_rows(a, every, idx, None, jnp.asarray(s * rows))
              for a, s in ((pool.k, 1), (pool.v, -1))]
    if quant:
        srows = rng.random((L, 6, B, H)).astype(np.float32)
        arenas += [write_rows(a, every, idx, None, jnp.asarray(s * srows))
                   for a, s in ((pool.ks, 1), (pool.vs, 2))]
    pos = jnp.asarray([2, 5], jnp.int32)
    # slot 0 accepted node 3 at depth 1 and node 2 (spine) at depth 2;
    # slot 1 stays on the spine (identity rows)
    src = jnp.asarray([[3, 2], [1, 2]], jnp.int32)
    out = _tree_commit_paged(src, pos, tables, *arenas, n_heads=H)
    blocks = [(B, H, D)] * 2 + [(B, H)] * 2
    for before, after, block in zip(arenas, out, blocks):
        b = np.asarray(read_chain(before, every, tables, block))  # (L, S, 3B, ..)
        a = np.asarray(read_chain(after, every, tables, block))
        want = b.copy()
        want[:, 0, 2 + 1] = b[:, 0, 2 + 3]      # position 3 <- offset 5 (next block)
        want[:, 0, 2 + 2] = b[:, 0, 2 + 2]
        np.testing.assert_array_equal(a, want)
        assert a.shape[2] == 3 * B


def test_tree_int8_kv_pool_matches_its_plain_decode(lm_model):
    """An int8 KV pool under tree verify: candidate rows are quantized
    into the data and scale arenas, accepted alternates are committed
    through both, and greedy streams equal the same int8 pool's plain
    decode engine token for token."""
    kw = dict(slots=4, cache_len=48, block_len=4, max_new_tokens=10,
              prefill_buckets=(8, 16), kv_quant="int8")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 32, size=n).astype(np.int32)
               for n in (5, 8, 11)]
    outs = []
    for spec in (None, SpecConfig(k=3, tree=True)):
        eng = LMServingEngine(lm_model, spec=spec, **kw)
        eng.warmup()
        try:
            streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
            outs.append([np.asarray(s.result(timeout=60)) for s in streams])
            if spec is not None:
                assert eng.stats()["spec"]["tree_rounds"] > 0
        finally:
            eng.close()
    for plain, tree in zip(*outs):
        np.testing.assert_array_equal(plain, tree)


# --------------------------------------------------------------------------- #
# bounded executables + donation                                              #
# --------------------------------------------------------------------------- #

def test_tree_bounded_executables_and_donation(tree_engine):
    """Exactly one donated verify executable per ladder rung (all
    warmed ahead of traffic), one commit executable, one drafter decode
    — and more traffic compiles nothing new; the donated arenas keep
    their buffers."""
    n_shapes = len(tree_engine._tree_shapes)
    assert tree_engine._verify_compiles == n_shapes
    assert tree_engine._commit_compiles == 1
    ptrs = tree_engine.cache_buffer_pointers()
    p = np.asarray([2, 4, 8], np.int32)
    tree_engine.submit(p, max_new_tokens=8).result(timeout=60)
    assert tree_engine._verify_compiles == n_shapes
    assert tree_engine._commit_compiles == 1
    assert tree_engine.draft.decode_compiles == 1
    assert tree_engine.cache_buffer_pointers() == ptrs
    st = tree_engine.stats()["spec"]
    assert st["verify_compiles"] == n_shapes


# --------------------------------------------------------------------------- #
# adaptive lifecycle: collapse -> demote -> re-probe                          #
# --------------------------------------------------------------------------- #

def _zero_drafter(vocab=31):
    """All-zero params: constant logits rows, so the spine drafts are
    always token 0 and the stable-argsort alternates are tokens 1, 2
    (1-based ids 1, 2, 3)."""
    import jax
    import jax.numpy as jnp
    bad = _lm(vocab=vocab, seed=1)
    bad.params = jax.tree_util.tree_map(jnp.zeros_like, bad.params)
    return bad


@pytest.mark.faults
def test_tree_acceptance_collapse_demotes_and_reprobes(lm_model):
    """Deterministic collapse under tree budgets: the zero drafter's
    spine AND alternates never match (the reference stream emits no
    1-based 1/2/3), so the slot steps down the ladder, demotes, then
    re-probes at ``init_rung`` — and the stream stays the offline
    trajectory throughout."""
    p = np.asarray([8, 10, 27, 14, 9, 26], np.int32)
    ref = _ref(lm_model, p, 24)
    assert not {0, 1} & set(ref[len(p):].tolist())  # determinism premise
    eng = LMServingEngine(lm_model, slots=1, cache_len=48, block_len=4,
                          max_new_tokens=24, prefill_buckets=(8,),
                          spec=SpecConfig(k=3, tree=True,
                                          draft=_zero_drafter(),
                                          ema_alpha=0.5, demote_below=0.5,
                                          stepdown_below=0.5,
                                          promote_above=1.0,
                                          min_rounds=2, probe_interval=3))
    eng.warmup()
    try:
        out = eng.submit(p, max_new_tokens=24).result(timeout=60)
        np.testing.assert_array_equal(out, ref)
        spec = eng.stats()["spec"]
        assert spec["acceptance_rate"] == 0.0
        assert spec["demotions"] >= 2   # collapsed, re-probed, collapsed
        assert spec["reprobes"] >= 1
        assert spec["rolled_back"] == spec["drafted"] > 0
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# the serving.verify fault site on tree rounds                                #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
def test_tree_verify_fault_demotes_not_kills(lm_model, monkeypatch):
    """An injected transient during a TREE verify round demotes the
    speculating slots and the round serves plain — the stream completes
    bit-exact, the demotion is typed and counted (PR 10's fault matrix,
    extended to tree mode)."""
    from bigdl_tpu.resilience import faults
    monkeypatch.setenv(faults.ENV_SPEC, "serving.verify:transient:count=1")
    faults.refresh_from_env()
    try:
        eng = LMServingEngine(lm_model, slots=2, cache_len=48,
                              block_len=4, max_new_tokens=16,
                              prefill_buckets=(8,),
                              spec=SpecConfig(k=3, tree=True,
                                              probe_interval=2))
        eng.warmup()
        try:
            p = np.arange(1, 7).astype(np.int32)
            out = eng.submit(p, max_new_tokens=16).result(timeout=60)
            np.testing.assert_array_equal(out, _ref(lm_model, p, 16))
            spec = eng.stats()["spec"]
            assert spec["fault_demotions"] == 1
            assert spec["reprobes"] >= 1
            snap = get_registry().snapshot()
            assert snap["serving/lm/spec/fault_demotions"]["value"] >= 1
        finally:
            eng.close()
    finally:
        monkeypatch.delenv(faults.ENV_SPEC, raising=False)
        faults.refresh_from_env()


# --------------------------------------------------------------------------- #
# the n-gram drafter                                                          #
# --------------------------------------------------------------------------- #

def test_ngram_drafter_determinism_and_vocab_guard():
    d = NgramDrafter(31, slots=2, ngram_max=3)
    ctx = [5, 6, 7, 5, 6, 7, 5, 6]
    d.admit(0, np.asarray(ctx, np.int32))
    jobs = {0: (4, 0.0, None, (1, 1))}
    a = d.draft_round(jobs)
    b = d.draft_round(jobs)          # pure function of slot history
    assert a == b
    spine, rows, alts = a[0]
    assert rows is None and len(spine) == 4 and len(alts) == 4
    assert spine[:2] == [7, 5]       # suffix [5, 6] continues 7, 5, ...
    assert d.steps == 0 and d.decode_compiles == 0 and d.arena_bytes == 0
    # vocab guard: out-of-range ids fail loudly at ingestion
    with pytest.raises(ValueError, match="vocab"):
        d.admit(1, np.asarray([3, 31], np.int32))
    d.admit(1, np.asarray([3, 4], np.int32))
    with pytest.raises(ValueError, match="vocab"):
        d.push(1, -1)
    with pytest.raises(ValueError, match="vocab"):
        d.commit(1, 0, [99])
    # no-match context: deterministic filler (last token) pads the spine
    d.release_all()
    d.admit(0, np.asarray([1, 2, 3], np.int32))
    spine, _, _ = d.draft_round({0: (3, 0.0, None)})[0]
    assert spine == [3, 3, 3]


def test_tree_ngram_engine_exact_and_free(lm_model):
    """The prompt-lookup regime end to end: greedy streams settle into
    the tiny model's attractor cycle, which suffix matching predicts —
    streams stay bit-exact with ZERO drafter decode steps and non-zero
    acceptance."""
    eng = LMServingEngine(lm_model, slots=2, cache_len=48, block_len=4,
                          max_new_tokens=24, prefill_buckets=(8, 16),
                          spec=SpecConfig(k=4, tree=True,
                                          drafter_compute="ngram"))
    eng.warmup()
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 32, size=10).astype(np.int32)
                   for _ in range(3)]
        streams = [eng.submit(p, max_new_tokens=24) for p in prompts]
        for p, s in zip(prompts, streams):
            np.testing.assert_array_equal(s.result(timeout=60),
                                          _ref(lm_model, p, 24))
        spec = eng.stats()["spec"]
        assert spec["draft_steps"] == 0          # the whole point
        assert spec["draft"]["compute_mode"] == "ngram"
        assert spec["accepted"] > 0
        assert spec["draft"]["hit_rate"] > 0.0
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# metrics exposure                                                            #
# --------------------------------------------------------------------------- #

def test_tree_metrics_published(tree_engine):
    snap = get_registry().snapshot()
    for key in ("tree_rounds", "alt_accepts", "tree_depth", "tree_width",
                "accepted_per_step", "accepted_per_verify_step"):
        assert ("serving/lm/spec/" + key) in snap
    st = tree_engine.stats()["spec"]
    assert st["tree"] is True
    assert st["tree_rounds"] > 0
    assert st["accepted_per_verify_step"] > 0
    assert st["tree_depth"]["count"] > 0
    assert st["tree_width"]["count"] > 0
    assert len(st["slot_rungs"]) == 4
