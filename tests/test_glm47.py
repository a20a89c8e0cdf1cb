"""GLM-4.7-Flash through ``TransformerLM`` and ``LMServingEngine`` at a toy
size, against the plain reference the benchmark keeps
(``benchmarks/harness/reference_glm47.py``: every layer's latent attention
EXPANDED with a full masked score matrix and a compressed query, the router's
steps literal, the prediction module as DeepSeek-V3's report writes it):
hidden 64, 4 heads, query rank 24, latent 24, 16 + 8 score lanes, 24 value
lanes, a leading dense layer and 3 routed ones (8 sigmoid-routed experts top-2,
all held, a shared expert) and the prediction module -- SERVED WITH THAT MODULE
AS THE DRAFTER, through the target's own latent pool.

LOGITS are compared, not tokens.  Tolerances, each with its reason:

- ``TOL`` 2e-4 on logits of size 0.6: both sides compute in float32 on the CPU
  (the program at XLA's default, full float32 there; the reference at
  ``highest``) and differ by the order of their sums and, in the served path,
  by the ABSORBED form at W = 2 against the reference's expanded one: 2e-6 to
  2e-5 read on this toy (both forwards, a bucket-padded prefill, a chunked one,
  a suffix prefill over a radix hit and the self-drafting rounds); 2e-4 leaves
  ten times that and is under a fifth of what latent rows kept in bfloat16
  move the logits (1.3e-3 read):
  ``test_a_lower_precision_latent_row_fails_the_tolerance``.
- ``FORM_TOL`` 2e-5 on attention outputs of size 1: the absorbed and the
  expanded form are two orders of the same float32 products.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_glm47 as D
from benchmarks.drivers import serve_ling3
from benchmarks.harness import reference_glm47 as R
from benchmarks.tests import toy_glm47, toy_ling3
from bigdl_tpu.models.transformer import generate as G
from bigdl_tpu.serving import lm_engine
from bigdl_tpu.serving.spec import SpecConfig

TOL = 2e-4
FORM_TOL = 2e-5
SEED = 5

toy = toy_glm47.config


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 96, size=(n,)).astype(np.int32)


@pytest.fixture(scope="module")
def reference_weights():
    return R.make_weights(SEED, toy(), "float32")


def _model(c):
    model = D.build_model(c)
    model.params = D.program_params(model, SEED, c, "float32")
    model.buffers = {}
    return model.evaluate()


class Rounds:
    """The engine's self-drafting round stood in for by the same step handing
    out its logits beside its ids: every round's operands, outputs, the two
    rows' logits and the draft's, by slot and stream."""

    def __init__(self, monkeypatch, engine):
        self.engine, self.seen = engine, []
        step = jax.jit(
            lambda p, ops, hid, prev, *kv: self._step(p, ops, hid, prev, *kv),
            donate_argnums=(4,))

        def stand_in(params, operands, hid, prev, *kv):
            ops = jnp.asarray(np.array(operands))
            # what the step makes of its operands: a chained slot's tokens,
            # position and n_cand come from the round before, on the device
            tokens, pos, n_cand, fresh, *_ = (
                np.asarray(a) for a in lm_engine.split_selfdraft_operands(
                    ops, engine.slots, prev))
            out, *rest = step(params, ops, hid, prev, *kv)
            *rest, logits, draft_logits = rest
            for i in np.nonzero(n_cand)[0]:
                self.seen.append(dict(
                    stream=engine._slots[i].stream, pos=int(pos[i]),
                    n_cand=int(n_cand[i]), fresh=bool(fresh[i]),
                    tokens=tokens[i].copy(), out=np.array(out[i]),
                    logits=np.array(logits[i]),
                    draft_logits=np.array(draft_logits[i])))
            return (out, *rest)

        monkeypatch.setattr(engine, "_verify_exec", stand_in)

    def _step(self, p, ops, hid, prev, *kv):
        eng = self.engine
        tokens, pos, n_cand, fresh, temperature, keys, live = (
            lm_engine.split_selfdraft_operands(ops, eng.slots, prev))
        return G._selfdraft_step_paged(
            eng.model, p, tokens, pos, n_cand, fresh, temperature, keys, hid,
            live, *kv, table_width=eng.table_width, attn_impl=eng.decode_attn,
            with_logits=True)

    def of(self, stream):
        return [r for r in self.seen if r["stream"] is stream]


def _reference(weights, c, out):
    """Both models' logits over a served sequence (1-based ``out``)."""
    logits, _, mtp, rows = R.forward(weights, c, np.asarray(out) - 1, both=True)
    return np.asarray(logits), np.asarray(mtp), np.asarray(rows)


# -- (a) the model as the configuration states it -------------------------------------
def test_the_plan_is_a_dense_layer_three_routed_ones_and_the_module():
    model = D.build_model(toy())
    (dense, lead), (repeat, period) = model.plan
    assert dense == 1 and [(s.mixer, s.mlp) for s in lead] == [("mla", "dense")]
    # one period of the three, unrolled: a scan would copy a layer's experts
    assert repeat == 1 and [(s.mixer, s.mlp) for s in period] == [("mla", "moe")] * 3
    assert model.latent_layers == (0, 1, 2, 3) and model.kv_layers == ()
    assert model.state_layers == () and model.moe_layers == 3
    assert model.mla.q_rank == 24 and model.mla.row == 32 and model.mla.score_dim == 24
    assert (model.mtp.mixer, model.mtp.mlp) == ("mla", "moe")
    assert model.moe.held == (0, 8) and model.moe.score == "sigmoid"
    assert not model.attn_gate and not model.tie_embeddings


@pytest.mark.parametrize("n", [45, 7])
def test_both_forwards_match_the_reference(reference_weights, n):
    """The whole-sequence forwards, EXPANDED on both sides: the main model's
    log-probabilities and the prediction module's logits (row t, of the pair
    (hidden at t, token at t + 1), scores the token at t + 2)."""
    c = toy()
    model, ids = _model(c), _ids(n)
    logits, _, mtp, _ = R.forward(reference_weights, c, ids, both=True)
    got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(jax.nn.log_softmax(logits) - got))) < TOL
    got = model.mtp_forward(model.params, jnp.asarray(ids[None] + 1))[0]
    assert got.shape == (n - 1, 96) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(mtp - got))) < TOL


def test_the_built_model_initialises_and_runs():
    """``build()`` as any user's model: the generic initialiser's compressed
    query and its prediction module."""
    model = D.build_model(toy()).build(seed=3)
    x = jnp.asarray(_ids(20)[None] + 1)
    y = model.f(model.params, x)
    assert y.shape == (1, 20, 96) and bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.all(jnp.isfinite(model.mtp_forward(model.params, x))))
    mp = model.params["groups"][1][0]["mla"]
    assert "wq" not in mp and mp["wq_a"].shape == (1, 64, 24)
    assert mp["q_norm"].shape == (1, 24) and mp["wq_b"].shape == (1, 24, 4 * 24)
    assert len(model.params["groups"][1]) == 3
    m = model.params["mtp"]
    assert m["eh_proj"].shape == (128, 64) and set(m) == {
        "enorm", "hnorm", "eh_proj", "block", "norm"}
    assert m["block"]["mla"]["wq_a"].shape == (64, 24)        # one block, unstacked
    assert m["block"]["moe"]["w_gate"].shape == (8, 64, 32)


@pytest.mark.parametrize("bad,says", [
    ({"mtp": (2, None, None, "dense", "attention")}, "one 'mla' block"),
    ({"mtp": (2, None, None, "moe", "mla")}, "one 'mla' block"),
    ({"mtp": (2, 4, None, "dense", "mla")}, "one 'mla' block"),
])
def test_the_constructor_names_what_a_prediction_module_is(bad, says):
    from bigdl_tpu.models.transformer import LayerSpec, MLASpec, TransformerLM
    with pytest.raises(ValueError, match=says):
        TransformerLM(64, hidden_size=32, n_head=2, n_layers=1, max_len=32,
                      pos_encoding="none", bias=False, mla=MLASpec(24, 16, 8, 16),
                      layer_plan=[(1, (LayerSpec(2, mixer="mla"),))], **bad)


def test_no_query_rank_is_lings_layer_bit_for_bit():
    """``q_rank`` ``None`` (or 0, or left out) is the one query matrix Ling's
    layers have: the same parameters, the same programs, the same bits."""
    from bigdl_tpu.models.transformer import MLASpec
    c = toy_ling3.config()
    ids = jnp.asarray(_ids(30)[None] + 1)
    outs = []
    for spec in (None, MLASpec(24, 16, 8, 16), MLASpec(24, 16, 8, 16, None),
                 MLASpec(24, 16, 8, 16, 0)):
        model = serve_ling3.build_model(c)
        if spec is not None:
            assert model.mla == MLASpec(24, 16, 8, 16) and model.mla.q_rank is None
            model.mla = spec
        model.params = serve_ling3.program_params(model, SEED, c, "float32")
        model.buffers = {}
        outs.append(np.asarray(model.evaluate().f(model.params, ids)))
        assert set(model.build(seed=1).params["groups"][1][3]["mla"]) == {
            "wq", "w_dkv", "kv_norm", "w_ukv", "wo", "wg"}
    assert all((o == outs[0]).all() for o in outs[1:])


# -- (b) the two forms of the latent layer at W = 2 --------------------------------------
def test_absorbed_is_expanded_at_two_rows_a_slot():
    """The verify rows' form: W = 2 new positions a slot, queries folded
    through W_uk against the cached rows and themselves, W_uv after the
    softmax, against the up-projected keys and values of the whole sequence."""
    model = _model(toy())
    spec = model.plan[1][1][0]
    bp = jax.tree_util.tree_map(lambda a: a[0], model.params["groups"][1][1])
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 21, 64))
    q, row, _ = model.mla_inputs(spec, bp, x, jnp.arange(21))
    want = model.attend_latent(bp, q, row)[:, :, -2:]           # (B, H, 2, v)
    m = model.mla
    qa = model.mla_absorb(bp, q[:, :, -2:])                     # (B, H, 2, 32)
    s = jnp.einsum("bhwr,bjr->bhwj", qa, row) / jnp.sqrt(jnp.float32(m.score_dim))
    seen = jnp.arange(21)[None, :] <= jnp.asarray([19, 20])[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    got = model.mla_values(bp, jnp.einsum("bhwj,bjr->bhwr", p, row[..., :m.kv_rank]))
    assert got.shape == (3, 4, 2, 24)
    assert float(jnp.max(jnp.abs(got - want))) < FORM_TOL


# -- (c) served with its own module as the drafter -----------------------------------------
@pytest.fixture(scope="module")
def engine():
    eng = D.build_engine(toy(), SEED)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def plain_engine():
    eng = D.build_engine(toy(), SEED, spec=None)
    yield eng
    eng.close()


def test_the_drafter_shares_the_targets_pool_and_nothing_else_is_allocated(engine,
                                                                           plain_engine):
    pool = engine.pool
    assert pool.latent and pool.v is None and len(engine._arenas()) == 1
    assert pool.n_layers == 5 and plain_engine.pool.n_layers == 4   # the module's, last
    assert engine.draft.arena_bytes == 0 and not hasattr(engine.draft, "k")
    assert engine.radix is not None and engine.stats()["prefix_cache"] == "on"
    spec = engine.stats()["spec"]
    assert spec["drafter"] == "prediction module" and spec["shares_pool"]
    assert spec["draft"]["arena_layer"] == 4 and spec["k"] == 1
    assert plain_engine.stats()["spec"] is None and plain_engine.draft is None
    assert engine._hid.shape == (4, 64)


def test_prefill_then_self_drafting_rounds_match_the_reference(
        monkeypatch, engine, reference_weights):
    """Through ``LMServingEngine.submit``: prompts under a bucket, over the
    largest (chunks, the second reading its prefix from the arena) and two
    that share a radix prefix; then self-drafting rounds, slots advancing by
    one or two, idle slots beside them: every logits row the round picked a
    token from, and every draft's, against the reference's two full forwards
    over what was served."""
    c = toy()
    rounds = Rounds(monkeypatch, engine)
    shared = _ids(12, 30)
    prompts = [_ids(5, 21), _ids(37, 22), np.concatenate([shared, _ids(6, 23)]),
               np.concatenate([shared, _ids(3, 24)])]
    first = engine.submit(prompts[2] + 1, max_new_tokens=10)
    first.result(timeout=300)           # the prefix is cached before the others
    streams = [engine.submit(p + 1, max_new_tokens=n)
               for p, n in zip(prompts, (14, 9, 10, 12))]
    outs = [s.result(timeout=300) for s in streams]
    assert (outs[2] == first.result()).all()
    assert engine.stats()["prefix_tokens"]["matched_tokens"] >= 24
    widths, accepted = set(), 0
    for prompt, stream, out in zip(prompts, streams, outs):
        logits, mtp, _ = _reference(reference_weights, c, out)
        seen = rounds.of(stream)
        assert seen and seen[0]["fresh"] and not any(r["fresh"] for r in seen[1:])
        for r in seen:
            p, acc = r["pos"], int(r["out"][2])
            assert np.max(np.abs(r["logits"][0] - logits[p])) < TOL
            if acc:
                assert r["n_cand"] == 2 and r["tokens"][1] == r["out"][0]
                assert np.max(np.abs(r["logits"][1] - logits[p + 1])) < TOL
            # the draft is the module's over the pair of the last kept row
            assert np.max(np.abs(r["draft_logits"] - mtp[p + acc])) < TOL
            widths.add(1 + acc)
            accepted += acc
    assert widths == {1, 2} and accepted >= 5       # slots advanced by 1 and by 2


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_served_tokens_with_the_drafter_are_the_plain_engines(engine, plain_engine,
                                                              temperature):
    """Greedy and sampled (``replay``): the drafter moves how many rounds a
    stream takes, never a token."""
    jobs = [(_ids(n, 50 + i) + 1, m) for i, (n, m) in enumerate(
        [(6, 17), (23, 12), (40, 9), (9, 20), (14, 5)])]
    kw = dict(temperature=temperature)
    got = [engine.submit(p, max_new_tokens=m, rng=7 + i, **kw)
           for i, (p, m) in enumerate(jobs)]
    want = [plain_engine.submit(p, max_new_tokens=m, rng=7 + i, **kw)
            for i, (p, m) in enumerate(jobs)]
    for g, w in zip(got, want):
        assert (g.result(timeout=300) == w.result(timeout=300)).all()
    assert any(s.drafts for s in got) and not any(s.drafts for s in want)
    before = engine.stats()["spec"]
    assert before["accepted"] > 0 and before["rolled_back"] > 0


def test_self_drafting_rounds_run_ahead_where_the_host_is_not_needed(
        monkeypatch, engine, plain_engine):
    """A greedy slot with more than two tokens left rides a round enqueued
    BEHIND the one on the device: the host hands it no token, no draft and no
    ``n_cand`` (``chain``; the step takes them from the round before, which
    the tests above read row by row at its TRUE position), and the client
    reads what synchronous rounds serve.  A slot that may have ended with the
    round in flight (two tokens or fewer left before it) is not chained, and
    a sampled slot keeps the rounds synchronous (its next keys follow how many
    tokens it emitted)."""
    real, seen = engine._verify_compiled(), []

    def spy(params, operands, hid, prev, *kv):
        split = lm_engine.selfdraft_operands(
            engine.slots, engine.slots * engine.table_width)
        split[0][:] = np.asarray(operands)
        seen.append({k: split[j].copy() for k, j in (
            ("tokens", 1), ("n_cand", 3), ("chain", 8), ("remaining", 9))})
        return real(params, operands, hid, prev, *kv)

    monkeypatch.setattr(engine, "_verify_exec", spy)
    prompt, before = _ids(9, 71) + 1, engine.metrics.rounds_ahead
    out = engine.submit(prompt, max_new_tokens=24).result(timeout=300)
    assert (out == plain_engine.submit(prompt, max_new_tokens=24).result(
        timeout=300)).all()
    ahead = [r for r in seen if r["chain"].any()]
    assert len(ahead) >= 4 and engine.metrics.rounds_ahead - before == len(ahead)
    for r in ahead:
        i, = np.nonzero(r["chain"])
        assert (r["remaining"][i] > 2).all()
        assert not r["tokens"][i].any() and not r["n_cand"][i].any()
    seen.clear()
    before = engine.metrics.rounds_ahead
    engine.submit(prompt, max_new_tokens=24, temperature=0.8, rng=3).result(
        timeout=300)
    assert seen and not any(r["chain"].any() for r in seen)
    assert engine.metrics.rounds_ahead == before


def test_a_stream_that_ends_on_its_eos_under_a_round_in_flight(engine, plain_engine):
    """The eos cannot be known when the next round is enqueued: the stream's
    slot rides it, its row is thrown away, and the streams beside it go on
    as if nothing had happened; what follows into the freed slot reads the
    plain engine's tokens too."""
    jobs = [(_ids(7, 80 + i) + 1, 22) for i in range(3)]
    plain = [plain_engine.submit(p, max_new_tokens=m).result(timeout=300)
             for p, m in jobs]
    eos = int(plain[0][9])
    want = [plain_engine.submit(p, max_new_tokens=m, eos_id=eos).result(timeout=300)
            for p, m in jobs]
    want.append(plain_engine.submit(jobs[0][0], max_new_tokens=8).result(timeout=300))
    assert len(want[0]) <= 10 < len(want[1])
    before = engine.metrics.rows_discarded
    got = [engine.submit(p, max_new_tokens=m, eos_id=eos) for p, m in jobs]
    got.append(engine.submit(jobs[0][0], max_new_tokens=8))
    for g, w in zip(got, want):
        assert (g.result(timeout=300) == w).all()
    assert engine.metrics.rows_discarded > before


def test_the_drafts_are_the_reference_modules_picks(engine, reference_weights):
    """Every draft the engine verified is what the plain prediction module
    scores best over the served sequence, and the acceptance the engine counts
    is what the reference's two forwards give."""
    c = toy()
    prompt = _ids(19, 61)
    stream = engine.submit(prompt + 1, max_new_tokens=40)
    out = stream.result(timeout=300)
    logits, mtp, _ = _reference(reference_weights, c, out)
    t, gen = len(prompt), out[len(prompt):] - 1
    assert len(stream.drafts) >= 15
    agree = []
    for i, draft in stream.drafts:
        row = mtp[t + i - 2]            # the pair (hidden at t + i - 2, token t + i - 1)
        assert row.max() - row[draft - 1] < TOL
        agree.append(int(np.argmax(row) == np.argmax(logits[t + i - 1])))
    got = [int(gen[i] == d - 1) for i, d in stream.drafts]
    assert got == agree and 0 < sum(got) < len(got)


def test_a_round_hands_the_host_ids_and_counts_and_no_logits(engine):
    before = engine.stats()["metrics"]
    engine.submit(_ids(11, 70) + 1, max_new_tokens=12).result(timeout=300)
    after = engine.stats()["metrics"]
    # one row of logits an admission (the first token's), none a round
    assert after["logit_rows_to_host"] - before["logit_rows_to_host"] == 1
    assert after["decode_steps"] > before["decode_steps"]
    exe = engine._verify_compiled()
    shapes = [tuple(a.shape) for a in jax.tree_util.tree_leaves(exe.out_info)]
    assert shapes[0] == (4, 4) and shapes[1] == (3,)        # ids and counts, MoE integers
    assert shapes[2:] == [engine.pool.shape] and engine._verify_compiles == 1


def test_every_draft_rejected_is_the_plain_stream(monkeypatch, plain_engine):
    """Rejection is a pointer rewind: a module whose every draft is wrong (its
    logits negated) leaves rows above every slot's position round after round,
    and the slots serve the plain engine's tokens."""
    from bigdl_tpu.models.transformer import TransformerLM
    real = TransformerLM.mtp_logits
    monkeypatch.setattr(TransformerLM, "mtp_logits",
                        lambda self, params, g: -real(self, params, g))
    eng = D.build_engine(toy(), SEED)
    try:
        jobs = [(_ids(n, 80 + i) + 1, m) for i, (n, m) in enumerate(
            [(7, 15), (30, 11), (12, 18)])]
        got = [eng.submit(p, max_new_tokens=m) for p, m in jobs]
        want = [plain_engine.submit(p, max_new_tokens=m) for p, m in jobs]
        for g, w in zip(got, want):
            assert (g.result(timeout=300) == w.result(timeout=300)).all()
        spec = eng.stats()["spec"]
        assert spec["drafted"] >= 30 and spec["accepted"] == 0
        assert spec["rolled_back"] == spec["drafted"]
        # a stream of n tokens took n - 1 rounds: one token a slot and round
        assert spec["emitted"] == sum(m - 1 for _, m in jobs)
    finally:
        eng.close()


def _chains(monkeypatch, eng) -> dict:
    """Every seated stream's pool chain, kept as it is seated (an ended
    stream's rows stay where they lay until a later one's overwrite them)."""
    chains, seat = {}, eng._seat

    def seated(req, t, first0, blocks, slot):
        chains[id(req.stream)] = list(blocks)
        return seat(req, t, first0, blocks, slot)

    monkeypatch.setattr(eng, "_seat", seated)
    return chains


def test_the_radix_cache_over_the_latent_pool_changes_nothing(monkeypatch,
                                                              reference_weights):
    """Requests that share a prefix, the cache on and off: the same tokens,
    the same DRAFTS and the same latent rows, the module's layer's too (its
    row at a position is of the pair BEFORE it, so a shared prefix's rows are
    every sharer's; the pair at the boundary is computed from the hidden state
    of the last matched position, which one token's pass recomputes), and the
    rows are the reference's."""
    c = toy()
    shared = _ids(16, 90)
    jobs = [np.concatenate([shared, _ids(n, 91 + i)]) for i, n in enumerate((5, 9, 2))]
    got = {}
    for cache in (True, False):
        eng = D.build_engine(c, SEED, enable_prefix_cache=cache)
        try:
            chains = _chains(monkeypatch, eng)
            eng.submit(jobs[0] + 1, max_new_tokens=4).result(timeout=300)
            streams = [eng.submit(p + 1, max_new_tokens=60) for p in jobs[1:]]
            outs = [s.result(timeout=300) for s in streams]
            at = np.arange(1, 30)
            rows = [np.asarray(eng.pool.rows_at(chains[id(s)], at)[0])
                    for s in streams]
            got[cache] = (outs, [s.drafts for s in streams], rows)
            matched = eng.stats()["prefix_tokens"]["matched_tokens"]
            assert matched == (32 if cache else 0)
        finally:
            eng.close()
    for a, b in zip(got[True][0], got[False][0]):
        assert (a == b).all()
    assert got[True][1] == got[False][1] and all(got[True][1])
    for a, b, out in zip(got[True][2], got[False][2], got[True][0]):
        assert a.shape == (5, 29, 32)
        assert np.max(np.abs(a - b)) < TOL
        _, _, mtp_rows = _reference(reference_weights, c, out)
        # the module's layer, last: position j holds pair j - 1's row
        assert np.max(np.abs(a[4] - mtp_rows[:29])) < TOL


def test_a_lower_precision_latent_row_fails_the_tolerance(monkeypatch,
                                                          reference_weights):
    """What ``TOL`` must refuse: the same engine with its latent rows -- the
    main layers' and the module's -- rounded to bfloat16 where float32 is
    stated."""
    c = toy()
    with toy_ling3.latent_rounded("bfloat16"):
        eng = D.build_engine(c, SEED)
        try:
            rounds = Rounds(monkeypatch, eng)
            stream = eng.submit(_ids(21, 95) + 1, max_new_tokens=16)
            out = stream.result(timeout=300)
            logits, mtp, _ = _reference(reference_weights, c, out)
            worst = max(np.max(np.abs(r["logits"][0] - logits[r["pos"]]))
                        for r in rounds.of(stream))
            drafts = max(np.max(np.abs(r["draft_logits"]
                                       - mtp[r["pos"] + int(r["out"][2])]))
                         for r in rounds.of(stream))
        finally:
            eng.close()
    assert worst > 3 * TOL and drafts > 3 * TOL, (worst, drafts)


# -- (d) the other drafters over a latent pool, and what is refused ----------------------------
def _latent_alone(**kw):
    from bigdl_tpu.models.transformer import (LayerSpec, MLASpec, RopeSpec,
                                              TransformerLM)
    spec = LayerSpec(2, rope=RopeSpec(theta=1e4, rotary_dim=8), mixer="mla")
    return TransformerLM(64, hidden_size=32, n_head=2, n_layers=2, max_len=64,
                         head_dim=16, pos_encoding="none", bias=False,
                         mla=MLASpec(24, 16, 8, 16, 12),
                         layer_plan=[(2, (spec,))], **kw).build(seed=1).evaluate()


def test_a_latent_pool_serves_with_the_ngram_drafter():
    """``("latent", "serve with spec")`` has left the table: the chain verify
    step attends a latent pool ABSORBED (W = k + 1 candidate rows a slot), so
    the n-gram drafter -- and any separate drafter -- serves a latent model."""
    from bigdl_tpu.serving import LMServingEngine
    model = _latent_alone()
    outs = []
    for spec in (None, SpecConfig(k=3, drafter_compute="ngram")):
        eng = LMServingEngine(model, slots=2, block_len=4, cache_len=64,
                              prefill_buckets=(8,), num_blocks=40, spec=spec)
        try:
            prompt = np.tile(_ids(5, 3) % 64 + 1, 3)        # a prompt that repeats
            outs.append(eng.submit(prompt, max_new_tokens=20).result(timeout=300))
            if spec is not None:
                assert eng.stats()["spec"]["drafter"] == "ngram"
                assert not eng.stats()["spec"]["shares_pool"]
                assert eng.stats()["spec"]["drafted"] > 0
        finally:
            eng.close()
    assert (outs[0] == outs[1]).all()


@pytest.mark.parametrize("spec,says", [
    (lambda: SpecConfig(k=1, tree=True), "tree verify"),
    (lambda: SpecConfig(k=1, sampling="rejection"), "rejection sampling"),
    (lambda: SpecConfig(k=2), "k > 1"),
    (lambda: 3, "k > 1"),
])
def test_what_the_prediction_module_refuses_as_the_drafter(spec, says):
    """Rows of the one table (``lm_engine.refuse_unsupported``), at
    construction."""
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    from bigdl_tpu.serving import LMServingEngine
    mtp = LayerSpec(2, rope=RopeSpec(theta=1e4, rotary_dim=8), mixer="mla")
    with pytest.raises(ValueError, match="its prediction module as the drafter "
                       "cannot serve with " + says) as e:
        LMServingEngine(_latent_alone(mtp=mtp), slots=2, block_len=4,
                        cache_len=64, prefill_buckets=(8,), num_blocks=40,
                        spec=spec())
    assert "M5" in str(e.value)


def test_a_latent_pool_refuses_tree_verify_and_a_module_serves_other_drafters():
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    from bigdl_tpu.serving import LMServingEngine
    kw = dict(slots=2, block_len=4, cache_len=64, prefill_buckets=(8,),
              num_blocks=40)
    with pytest.raises(ValueError, match="latent attention layers cannot serve "
                       "with tree verify"):
        LMServingEngine(_latent_alone(), spec=SpecConfig(
            k=2, tree=True, drafter_compute="ngram"), **kw)
    # a model WITH a module and the n-gram drafter named: the module rests
    mtp = LayerSpec(2, rope=RopeSpec(theta=1e4, rotary_dim=8), mixer="mla")
    model = _latent_alone(mtp=mtp)
    assert not lm_engine.drafts_for_itself(model, SpecConfig(
        k=2, drafter_compute="ngram"))
    assert lm_engine.drafts_for_itself(model, SpecConfig(k=1))
    assert lm_engine.drafts_for_itself(model, 1)
    assert not lm_engine.drafts_for_itself(model, None)
    assert not lm_engine.drafts_for_itself(_latent_alone(), SpecConfig(k=1))
    eng = LMServingEngine(model, spec=SpecConfig(k=2, drafter_compute="ngram"),
                          **kw)
    try:
        assert eng.pool.n_layers == 2 and not eng._selfdraft
    finally:
        eng.close()


# -- (e) spans, counters and the registry --------------------------------------------------------
def test_the_rounds_spans_and_counters(engine):
    from bigdl_tpu.obs import get_registry
    from bigdl_tpu.obs.tracer import get_tracer
    tracer = get_tracer()
    rate = tracer.sample_rate
    tracer.set_sample_rate(1.0)
    tracer.enable()
    tracer.clear()
    before = engine.stats()
    try:
        out = engine.submit(_ids(9, 99) + 1, max_new_tokens=13).result(timeout=300)
    finally:
        tracer.disable()
        tracer.set_sample_rate(rate)
    after = engine.stats()
    steps = [e["args"] for e in tracer.events() if e["name"] == "lm/verify_step"]
    marks = [e["args"] for e in tracer.events() if e["name"] == "lm/draft"]
    spec = {k: after["spec"][k] - before["spec"][k]
            for k in ("drafted", "accepted", "emitted", "tokens_emitted",
                      "verify_rounds", "draft_latent_rows_read", "draft_steps")}
    assert len(steps) == len(marks) == spec["verify_rounds"] > 0
    assert sum(a["emitted"] for a in steps) == spec["emitted"] == len(out) - 9 - 1
    assert spec["tokens_emitted"] == spec["emitted"]
    assert sum(a["drafted"] for a in steps) == spec["drafted"]
    assert sum(a["accepted"] for a in steps) == spec["accepted"]
    assert spec["emitted"] == spec["verify_rounds"] + spec["accepted"]
    assert all(m["fused"] == 1 for m in marks)
    assert sum(m["pairs"] for m in marks) == spec["draft_steps"]
    # every arena layer read: the four main ones and the module's
    latent = after["metrics"]["latent"]["rows_read"] - before["metrics"]["latent"]["rows_read"]
    assert sum(a["latent_positions"] for a in steps) == latent
    assert latent == 5 * spec["draft_latent_rows_read"]
    assert all({"active", "round", "live_blocks", "moe_assignments",
                "moe_experts_hit"} <= set(a) for a in steps)
    # 3 routed layers and the module's block a round
    moe = after["metrics"]["moe"]["expert_layer_rounds"] - before["metrics"]["moe"]["expert_layer_rounds"]
    assert moe == 4 * spec["verify_rounds"]
    reg = get_registry().snapshot()
    for key in ("drafted", "accepted", "tokens_emitted", "verify_rounds",
                "draft_latent_rows_read"):
        assert "serving/lm/spec/" + key in reg
    assert "serving/lm/prefix_matched_tokens" in reg
    names = jax.jit(lambda p, *a: G._selfdraft_step_paged(
        engine.model, p, *a, table_width=engine.table_width)).lower(
        engine._params, jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.ones((4,), jnp.int32), jnp.zeros((4,), bool),
        jnp.zeros((4,), jnp.float32), jnp.zeros((4, 4, 2), jnp.uint32),
        engine._hid, jnp.zeros((3, 8), jnp.int32),
        *engine.pool.arenas).as_text(debug_info=True)
    for scope in ("mtp/embed_proj", "mtp/block", "mtp/head", "mla/q_down",
                  "mla/absorb", "mla/attend"):
        assert scope in names, scope
