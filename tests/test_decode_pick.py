"""The decode step picks its tokens: a round hands the host ``(S,)`` ids, not
``(S, V)`` float32 logits (``generate.pick_next`` / ``pick_rows`` /
``_decode_pick_paged``, ``LMServingEngine._decode_fn`` and ``_dispatch`` / ``_collect``).

Toy sizes, the CPU, the GPT-2-shaped toy and the toy Laguna (grouped heads,
windows, routed experts).  The step: its ids are ``spec.verify.pick_token`` --
the host's twin of the device rule -- applied row by row to the same step's
logits, in one round that mixes greedy slots, sampled slots at two
temperatures and an idle slot; the routed layers' counts and the arenas ride
out as they did.  The engine: the streams of the one decode executable are
those of the old contract (logits to the host, the host's pick a row), mixed
rounds included; the executable has no output of the vocabulary's width; and
``LMMetrics.logit_rows_to_host`` counts 0 a plain decode round, 1 an
admission, ``S x W`` a verify round.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import generate as G
from bigdl_tpu.obs import get_registry
from bigdl_tpu.serving import LMServingEngine, lm_engine
from bigdl_tpu.serving.kvcache import blocks as KB
from bigdl_tpu.serving.spec import SpecConfig
from bigdl_tpu.serving.spec.verify import pick_token
from tests.test_live_list import (M, _class_arenas, _class_lists, _tables,
                                  _toy_gpt2, _toy_laguna)


def operands_of(operands, slots):
    """A round's one operand vector, as the step program splits it."""
    return [np.asarray(x) for x in lm_engine.split_decode_operands(
        jnp.asarray(operands), slots)]

MODELS = ["gpt2", "laguna"]


def _model(case):
    return _toy_laguna() if case == "laguna" else _toy_gpt2()


def _keys(n, seed):
    return np.array(jax.random.split(jax.random.PRNGKey(seed), n))


# -- the rule ----------------------------------------------------------------------
@pytest.mark.parametrize("temperature", [0.0, 1e-9, 0.6, 1.0, 1.7])
def test_device_rule_is_the_hosts_twin(temperature):
    """``pick_next`` at (1, V) under a slot's key is ``pick_token`` of that row
    (the clamped rule of a decode step), greedy and at every temperature; a
    row with a tie takes its first index, as ``np.argmax``."""
    rows = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (6, 61))) * 3.0
    rows[2, 7] = rows[2, 40] = rows[2].max() + 1.0          # a tie at the top
    keys = _keys(6, 2)
    for row, key in zip(rows, keys):
        got = int(G.pick_next(jnp.asarray(row)[None, :], jnp.asarray(key),
                              jnp.float32(temperature))[0])
        assert got == pick_token(row, temperature, key, clamp=True)
    ids = np.asarray(G.pick_rows(jnp.asarray(rows),
                                 jnp.full((6,), temperature, jnp.float32),
                                 jnp.asarray(keys)))
    assert ids.dtype == np.int32
    assert ids.tolist() == [pick_token(r, temperature, k, clamp=True)
                            for r, k in zip(rows, keys)]
    if temperature == 0.0:
        assert ids[2] == 7


def test_one_operand_vector_holds_what_a_round_hands_its_step():
    """The host fills views of ONE int32 vector (a transfer a round, not
    five); the step program splits the same layout, temperatures and keys
    bit for bit."""
    ops, token, pos, temperature, keys, live = lm_engine.decode_operands(4, 24)
    assert ops.dtype == np.int32 and ops.shape == (5 * 4 + 3 * 24,)
    assert not ops.any()                # zeros: greedy, idle, nobody's scratch
    token[:] = [3, 0, 17, 8]
    pos[:] = [13, 0, 5, 22]
    temperature[:] = [0.0, 0.0, 0.7, 1.3]
    keys[:] = _keys(4, 5)
    live[:] = KB.live_list([(0, [7, 3]), (2, [5, 9, 4])], 24, slots=4)
    got = operands_of(ops, 4)
    for a, b in zip(got, (token, pos, temperature, keys, live)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # a token the host has not seen yet: the sentinel in its place is no
    # token of any vocabulary, and rides the same vector
    assert lm_engine.TAKE_PREV < 0
    token[2] = lm_engine.TAKE_PREV
    assert operands_of(ops, 4)[0].tolist() == [3, 0, lm_engine.TAKE_PREV, 8]


# -- the step ----------------------------------------------------------------------
#: slot -> (position being written or None: idle, temperature)
ROUND = [(13, 0.0), (None, 0.0), (5, 0.7), (22, 1.3), (9, 0.0), (3, 0.7)]


@pytest.mark.parametrize("round_", ["mixed", "all-greedy"])
@pytest.mark.parametrize("case", MODELS)
def test_step_ids_are_pick_token_of_the_steps_logits(case, round_):
    """One round of six slots -- two greedy, an idle one, three that sample at
    two temperatures (``mixed``), or the same slots all greedy (the branch
    that draws no noise): the ids are ``pick_token`` of the logits' rows, the
    routed layers' two integers and the arenas those of the step that hands
    out logits.  Two of the slots take their token from ``prev_ids`` (the
    run-ahead round's ``TAKE_PREV``): the step is the one fed those tokens."""
    model = _model(case)
    where = [p for p, _ in ROUND]
    temps = np.asarray([t if round_ == "mixed" else 0.0 for _, t in ROUND],
                       np.float32)
    keys = _keys(len(ROUND), 9)
    keys[temps == 0.0] = 0              # what the engine hands a greedy slot
    pos = jnp.asarray([p or 0 for p in where], jnp.int32)
    _, chains = _tables(where, seed=3)
    live = _class_lists(model, KB.live_list(chains, len(ROUND) * M, len(ROUND)),
                        chains, where)
    arenas = _class_arenas(model, None, seed=4)
    token = jnp.asarray([3, 0, 17, 8, 40, 21], jnp.int32)
    # two slots' tokens are the previous step's picks, still on the device:
    # the sentinel in their place of the operand, the value in prev_ids
    taken = np.asarray([0, 0, 1, 0, 1, 0], bool)
    operand = jnp.where(taken, lm_engine.TAKE_PREV, token)
    prev_ids = jnp.where(taken, token, 59 - token)      # the others': ignored
    kw = dict(table_width=M)

    logits, *rest = jax.jit(lambda *a: G._decode_step_paged(
        model, model.params, *a, **kw))(token, pos, live, *arenas)
    ids, *picked = jax.jit(lambda *a: G._decode_pick_paged(
        model, model.params, *a, **kw))(operand, pos, live, jnp.asarray(temps),
                                        jnp.asarray(keys), prev_ids, *arenas)
    assert ids.shape == (len(ROUND),) and ids.dtype == jnp.int32
    logits = np.asarray(logits)
    want = [pick_token(logits[i], float(temps[i]), keys[i], clamp=True)
            for i in range(len(ROUND))]
    assert np.asarray(ids).tolist() == want
    if round_ == "mixed":       # the draws are draws: not the argmax everywhere
        sampled = [i for i, t in enumerate(temps) if t > 0]
        assert any(want[i] != int(np.argmax(logits[i])) for i in sampled)
    # what rides out beside the ids: the counts (a routed model), the arenas
    assert len(picked) == len(rest) == len(arenas) + bool(model.moe_layers)
    for a, b in zip(picked, rest):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if model.moe_layers:
        assert np.asarray(picked[0]).shape == (3,) and int(picked[0][0]) > 0


# -- the engine --------------------------------------------------------------------
def _engine(case, **kw):
    if case == "laguna":
        from benchmarks.drivers import serve_laguna as D
        from benchmarks.tests import toy_laguna
        c = toy_laguna.config()
        c["engine"].update(kw)
        return D.build_engine(c, 5)
    from bigdl_tpu.models.transformer import TransformerLM
    m = TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=2,
                      max_len=64).build(seed=7).evaluate()
    args = dict(slots=4, block_len=4, cache_len=64,
                prefill_buckets=(8, 16, 32), enable_prefix_cache=False)
    args.update(kw)
    return LMServingEngine(m, **args)


def _host_pick_exec(eng):
    """The old contract, as a stand-in for the engine's decode executable: the
    step hands its (S, V) logits to the host, and the host picks a row."""
    from bigdl_tpu.quant import dequantize_entry
    step = jax.jit(
        lambda p, token, pos, live, *kv: G._decode_step_paged(
            eng.model, dequantize_entry(p), token, pos, live, *kv,
            table_width=eng.table_width, attn_impl=eng.decode_attn),
        donate_argnums=tuple(range(4, 4 + len(eng.pool.arenas))))
    rounds = {"n": 0, "mixed": 0}

    def call(params, operands, prev_ids, *kv):
        token, pos, temperature, keys, live = operands_of(operands, eng.slots)
        token = np.where(token == lm_engine.TAKE_PREV, np.asarray(prev_ids),
                         token)
        logits, *rest = step(params, token, pos, live, *kv)
        logits = np.asarray(logits)
        ids = np.asarray([pick_token(logits[i], float(temperature[i]),
                                     keys[i], clamp=True)
                          for i in range(eng.slots)], np.int32)
        block, owner, _ = live
        busy = np.zeros(eng.slots, bool)
        busy[owner[block != KB.SCRATCH_BLOCK]] = True
        hot = temperature > 0
        rounds["n"] += 1
        rounds["mixed"] += bool((busy & hot).any() and (busy & ~hot).any()
                                and not busy.all())
        return (jnp.asarray(ids), *rest)

    return call, rounds


#: (prompt length, new tokens, temperature, rng seed): greedy and sampled at two
#: temperatures share the rounds; three requests on four slots leave one idle
MIX = [(5, 14, 0.0, None), (11, 12, 0.7, 3), (19, 10, 1.3, 4)]


@pytest.mark.parametrize("case", MODELS)
def test_streams_are_those_of_logits_picked_on_the_host(case):
    """Three requests served together -- greedy, sampled at 0.7 and at 1.3,
    a fourth slot idle -- and then a fifth alone: every stream of the engine's
    own executable (ids from the device) is the stream of the same engine with
    the old step in its place (logits to the host, ``pick_token`` a row).  At
    least one round held a greedy slot, a sampled one and an idle one."""
    eng = _engine(case)
    try:
        eng.warmup()
        assert eng._decode_exec is not None
        rng = np.random.RandomState(6)
        prompts = [rng.randint(1, eng.model.vocab_size, size=n)
                   for n, *_ in MIX]

        def serve():
            streams = [eng.submit(p, max_new_tokens=k, temperature=t, rng=seed)
                       for p, (_, k, t, seed) in zip(prompts, MIX)]
            got = [list(map(int, s.result(timeout=300))) for s in streams]
            got.append(list(map(int, eng.submit(
                prompts[1], max_new_tokens=6, temperature=0.9,
                rng=11).result(timeout=300))))
            return got

        before = eng.metrics.logit_rows_to_host
        device = serve()
        assert eng.metrics.logit_rows_to_host - before == len(MIX) + 1
        own = eng._decode_exec
        eng._decode_exec, rounds = _host_pick_exec(eng)
        host = serve()
        eng._decode_exec = own
        assert device == host
        assert rounds["n"] >= 13 and rounds["mixed"] >= 1
        # sampled streams are draws: they leave the greedy stream
        greedy = list(map(int, eng.submit(
            prompts[1], max_new_tokens=12).result(timeout=300)))
        assert greedy != device[1]
        if eng.model.moe_layers:        # the counts still ride out
            moe = eng.stats()["metrics"]["moe"]
            assert moe["expert_layer_rounds"] > 0 and moe["experts_hit"] > 0
    finally:
        eng.close()


@pytest.mark.parametrize("case", MODELS + ["int8"])
def test_decode_executable_has_no_output_of_the_vocabularys_width(case):
    """Its first output is (S,) int32; a routed model's integers and the
    donated arenas follow; nothing has V columns.  It takes the operand
    vector, then the previous step's ids -- its own first output's shape, not
    donated: the host still reads them -- then the donated arenas."""
    eng = (_engine("gpt2", kv_quant="int8") if case == "int8"
           else _engine(case))
    try:
        out = eng._decode_compiled().out_info
        vocab = eng.model.vocab_size
        assert out[0].shape == (eng.slots,) and out[0].dtype == jnp.int32
        assert len(out) == 1 + bool(eng.model.moe_layers) + len(eng.pool.arenas)
        if eng.model.moe_layers:
            assert out[1].shape == (3,)    # the row tiles ride last
        assert [o.shape for o in out[-len(eng.pool.arenas):]] == [
            a.shape for a in eng.pool.arenas]
        assert all(vocab not in o.shape
                   for o in out[:-len(eng.pool.arenas)])
        (_, operands, prev_ids, *kv), _ = eng._decode_compiled().in_avals
        # (a live list a class of blocks, side by side: whole tables, or
        # under a window the blocks a window touches a slot)
        assert operands.shape == (5 * eng.slots + 3 * sum(eng._live_entries),)
        assert eng._live_entries[0] == eng.slots * eng.table_width
        assert (prev_ids.shape, prev_ids.dtype) == (out[0].shape, jnp.int32)
        assert [a.shape for a in kv] == [a.shape for a in eng.pool.arenas]
        assert eng._ids.shape == prev_ids.shape         # zeros before a round
    finally:
        eng.close()


def test_logit_rows_to_host_counts_admissions_and_verify_rounds():
    """A plain engine: one row an admission, none for its decode rounds.  A
    speculating engine (k = 3, four slots): ``S x W`` = 16 rows a verify
    round beside the admissions' own.  The registry holds the counter."""
    eng = _engine("gpt2")
    try:
        rng = np.random.RandomState(8)
        prompts = [rng.randint(1, 62, size=n) for n in (5, 9, 14, 7, 20)]
        for s in [eng.submit(p, max_new_tokens=9) for p in prompts]:
            s.result(timeout=300)
        m = eng.stats()["metrics"]
        assert m["decode_steps"] >= 8 and m["prefills"] == 5
        assert m["logit_rows_to_host"] == 5
        assert (get_registry().snapshot()["serving/lm/logit_rows_to_host"]
                ["value"] == 5)
    finally:
        eng.close()
    eng = _engine("gpt2", spec=SpecConfig(k=3))
    try:
        for s in [eng.submit(p, max_new_tokens=9) for p in prompts[:3]]:
            s.result(timeout=300)
        rounds = eng.spec_metrics.verify_rounds
        assert rounds >= 2
        assert (eng.metrics.logit_rows_to_host
                == 3 + rounds * eng.slots * (eng.spec.k + 1))
    finally:
        eng.close()
