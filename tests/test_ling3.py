"""Ling-3.0-flash-VL's language model through ``TransformerLM`` and
``LMServingEngine`` at a toy size, against the plain reference the benchmark
keeps (``benchmarks/harness/reference_ling3.py``: the MLA layer EXPANDED with
a full masked score matrix, the KDA layer a literal scan over positions, the
router its five literal steps): hidden 64, 4 heads, KDA layers with a 16 x 16
state, a bounded decay and full-rank projections, one MLA layer in six (latent
24, 16 + 8 score lanes, 16 value lanes, a gate a head), two leading dense
layers and one period of six, 16 sigmoid-routed experts in 4 groups of which
2 stay, top-4, one group held, a shared expert.

LOGITS are compared, not tokens.  Tolerances, each with its reason:

- ``TOL`` 2e-4 on logits of size 0.7: both sides compute in float32 on the CPU
  (the program at XLA's default, full float32 there; the reference at
  ``highest``) and differ by the order of their sums -- and, in the served
  path, by the ABSORBED form against the reference's expanded one: 4e-6 to
  2e-5 read on this toy (the whole forward, a bucket-padded prefill and its
  decode rounds, a prompt prefilled in chunks against the latent arena); 2e-4
  leaves ten times that and is under a sixth of what a state kept in bfloat16
  (1.3e-3 read) or latent rows kept in bfloat16 (1.5e-3) move the logits:
  ``test_a_lower_precision_cache_fails_the_tolerance``.
- ``FORM_TOL`` 2e-5 on attention outputs of size 1: the absorbed and the
  expanded form are two orders of the same float32 products (2e-6 read).
- ``KDA_TOL`` 2e-5: tests/test_solar2.py's, for the chunked form against the
  literal scan at the decay's bound.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_ling3 as D
from benchmarks.drivers import serve_solar2
from benchmarks.harness import reference_ling3 as R
from benchmarks.tests import toy_ling3, toy_solar2
from benchmarks.tests.served import Served
from bigdl_tpu.nn import kda
from bigdl_tpu.parallel import expert as E

TOL = 2e-4
FORM_TOL = 2e-5
KDA_TOL = 2e-5
SEED = 5

toy = toy_ling3.config


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


def _want(weights, c, prompt, forced):
    ids = np.concatenate([prompt, forced])
    t = len(prompt)
    return np.asarray(R.forward(weights, c, ids))[t - 1:t - 1 + len(forced)]


# -- (a) the model as the configuration states it -------------------------------------
def test_layer_plan_is_two_dense_layers_and_a_period_of_five_kda_and_one_mla():
    model = D.build_model(toy())
    (dense, lead), (repeat, period) = model.plan
    assert dense == 2 and [(s.mixer, s.mlp) for s in lead] == [("kda", "dense")]
    assert repeat == 1 and [s.mixer for s in period] == ["kda"] * 3 + ["mla", "kda", "kda"]
    assert all(s.mlp == "moe" for s in period)
    assert model.kv_layers == () and model.latent_layers == (5,)
    assert model.state_layers == (0, 1, 2, 3, 4, 6, 7) and model.moe_layers == 6
    assert model.mla.row == 32 and model.mla.score_dim == 24
    assert model.kda.gate == "bounded" and model.kda.full_rank
    assert model.kda.beta_scale == 1.0 and model.attn_gate == "per-head"
    assert (model.moe.n_group, model.moe.topk_group, model.n_counts) == (4, 2, 4)
    assert period[3].rope.rotary_dim == 8 and period[0].rope is None


@pytest.mark.parametrize("n", [45, 64, 7])
def test_full_forward_matches_the_reference(reference_weights, n):
    """The training-side forward: under, at and over a chunk of the scan; the
    latent layer EXPANDED on both sides."""
    c = toy()
    model, ids = _model(c), _ids(n)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) < TOL


def test_the_built_model_initialises_and_runs():
    """``build()`` as any user's model: the generic initialiser's MLA block
    and the full-rank KDA block."""
    model = D.build_model(toy()).build(seed=3)
    y = model.f(model.params, jnp.asarray(_ids(20)[None] + 1))
    assert y.shape == (1, 20, 96) and bool(jnp.all(jnp.isfinite(y)))
    mp = model.params["groups"][1][3]["mla"]
    assert mp["wq"].shape == (1, 64, 4 * 24) and mp["w_dkv"].shape == (1, 64, 32)
    assert mp["w_ukv"].shape == (1, 24, 4 * 32) and mp["wg"].shape == (1, 64, 4)
    kp = model.params["groups"][0][0]["kda"]
    assert kp["wf"].shape == kp["wg"].shape == (2, 64, 64) and "wf1" not in kp


@pytest.mark.parametrize("bad,says", [
    ({"mla": None}, "needs mla=MLASpec"),
    ({"bias": True}, "no biases"),
    ({"kda": ("tanh",)}, "KDASpec.gate"),
])
def test_the_constructor_names_what_a_latent_layer_needs(bad, says):
    from bigdl_tpu.models.transformer import LayerSpec, MLASpec, TransformerLM
    kw = dict(mla=MLASpec(24, 16, 8, 16), bias=False)
    kw.update(bad)
    with pytest.raises(ValueError, match=says):
        TransformerLM(64, hidden_size=32, n_head=2, n_layers=1, max_len=32,
                      layer_plan=[(1, (LayerSpec(2, mixer="mla"),))], **kw)


# -- (b) the two forms of the latent layer ---------------------------------------------
def test_absorbed_is_expanded():
    """One function, two paths: queries folded through W_uk against the cached
    rows themselves, W_uv after the softmax, give what the up-projected keys
    and values give."""
    model = _model(toy())
    spec = model.plan[1][1][3]
    bp = jax.tree_util.tree_map(lambda a: a[0], model.params["groups"][1][3])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 37, 64))
    q, row, _ = model.mla_inputs(spec, bp, x, jnp.arange(37))
    want = model.attend_latent(bp, q, row)                    # (B, H, T, v)
    m = model.mla
    qa = model.mla_absorb(bp, q)                                    # (B, H, T, 32)
    assert qa.shape == (2, 4, 37, m.row)
    s = jnp.einsum("bhtr,bjr->bhtj", qa, row) / jnp.sqrt(jnp.float32(m.score_dim))
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((37, 37), bool)), s, -jnp.inf), -1)
    u = jnp.einsum("bhtj,bjr->bhtr", p, row[..., :m.kv_rank])
    got = model.mla_values(bp, u)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(got - want))) < FORM_TOL


# -- (c) served: prefill, then decoding through the latent pool and the state arena ----
@pytest.fixture(scope="module")
def engine():
    eng = D.build_engine(toy(), SEED)
    yield eng
    eng.close()


def test_the_pool_is_one_latent_arena_and_no_kv_arena_at_all(engine):
    pool = engine.pool
    assert pool.latent and pool.v is None and pool.ks is None
    assert pool.n_layers == 1 and pool.shape == (1, 100, 4, 128)    # 32 -> 128 lanes
    assert len(pool.arenas) == 1 and len(engine._arenas()) == 3     # rows, state, tail
    assert pool.kv_arena_bytes == pool.arena_bytes == 100 * 4 * 128 * 4
    assert engine.state.state.shape == (7, 4, 4, 16, 16)
    assert engine.radix is None and engine.decode_attn == "gather"
    assert engine._prefix_block_buckets == (engine.table_width,)
    stats = engine.stats()
    assert stats["kv_pool"]["row"] == "one latent row a position"
    assert stats["kv_pool"]["row_lanes"] == 32 and stats["kv_pool"]["row_bytes"] == 512
    assert stats["latent_cache"]["layers"] == 1 and stats["state"]["layers"] == 7
    assert stats["state"]["step_path"] == "xla"     # the CPU: ``kda_step``
    with pytest.raises(NotImplementedError, match="latent row"):
        pool.export_chain([1])


def test_a_kv_model_says_what_its_row_is():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    model = TransformerLM(64, hidden_size=32, n_head=2, n_layers=2,
                          max_len=32).build(seed=1).evaluate()
    eng = LMServingEngine(model, slots=2, block_len=4, cache_len=32,
                          prefill_buckets=(8,), enable_prefix_cache=False)
    try:
        stats = eng.stats()
        assert stats["kv_pool"]["row"] == "a (k, v) pair a K/V head"
        assert stats["latent_cache"] is None and not eng.pool.latent
        assert eng.pool.data_arenas == 2 and len(eng._arenas()) == 2
    finally:
        eng.close()


def test_prefill_then_decode_through_the_latent_pool_matches_the_reference(
        monkeypatch, engine, reference_weights):
    """Through ``LMServingEngine.submit``: a prompt of 11 (bucket 16: five
    padded positions neither the state nor a query may see), then 14 decode
    rounds ABSORBED, three idle slots beside it, against the reference's
    expanded full forward."""
    c, prompt, forced = toy(), _ids(11, 1), _ids(15, 2)
    before = engine.stats()["metrics"]["latent"]["rows_read"]
    served = Served(monkeypatch, engine)
    who, stream = served.submit(prompt, forced)
    stream.result(timeout=300)
    got = served.logits(who)
    assert got.shape == (15, 96)
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL
    # round i reads the 11 prompt rows and the i + 1 decoded ones
    latent = engine.stats()["metrics"]["latent"]
    assert latent["rows_read"] - before == sum(11 + i + 1 for i in range(14))
    assert latent["bytes_read"] == latent["rows_read"] * 512
    assert engine.stats()["latent_cache"]["blocks_used"] == 0       # it has finished


def test_mixed_rounds_idle_slots_and_a_reused_slot(monkeypatch, engine,
                                                   reference_weights):
    """Six requests over four slots, joining and leaving: rounds of four down
    to one active slots, two requests seated into slots another has just left,
    one of them prefilled in chunks."""
    c = toy()
    served = Served(monkeypatch, engine)
    jobs = [(_ids(n, 20 + i), _ids(m, 40 + i))
            for i, (n, m) in enumerate([(5, 4), (30, 9), (12, 13), (8, 6),
                                        (17, 7), (3, 11)])]
    jobs = [(np.concatenate([[i], p[1:]]).astype(np.int32), f)
            for i, (p, f) in enumerate(jobs)]       # told apart by their first token
    handles = [served.submit(p, f) for p, f in jobs]
    for (who, stream), (prompt, forced) in zip(handles, jobs):
        stream.result(timeout=300)
        got = served.logits(who)
        assert got.shape[0] == len(forced)
        assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL
    widths = {len(r) for r in served.rounds}
    assert 4 in widths and widths & {1, 2, 3}        # full rounds and idle slots
    seats = [i for r in served.rounds for i in r]
    assert len(set(seats)) == 4 and len(jobs) == 6   # so slots were reused


def test_a_prompt_over_the_bucket_reads_its_prefix_from_the_latent_arena(
        monkeypatch, engine, reference_weights):
    """45 tokens over a bucket of 16: a chunk of 16, then two suffix chunks
    (16 and 13) whose latent layer expands the cached prefix from the arena
    and whose KDA layers start from the slot's rows."""
    c, prompt, forced = toy(), _ids(45, 7), _ids(8, 8)
    before = engine.stats()["prefix_prefill_cache"]
    served = Served(monkeypatch, engine)
    who, stream = served.submit(prompt, forced)
    stream.result(timeout=300)
    after = engine.stats()["prefix_prefill_cache"]
    # ONE suffix executable whatever the prefix's length
    assert after["misses"] - before["misses"] <= 1
    assert (after["hits"] + after["misses"]) - (before["hits"] + before["misses"]) == 2
    got = served.logits(who)
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL


def test_the_prefix_walk_takes_steps_of_its_own(monkeypatch, reference_weights):
    """A prefix longer than one step of the walk: 40 cached positions in steps
    of 8 (five steps, the last suffix query's prefix not a whole number of
    them) read the same logits."""
    from bigdl_tpu.models.transformer import generate as G
    monkeypatch.setattr(G, "LATENT_PREFIX_STEP", 8)
    c, prompt, forced = toy(), _ids(45, 11), _ids(3, 12)
    eng = D.build_engine(c, SEED)
    try:
        served = Served(monkeypatch, eng)
        who, stream = served.submit(prompt, forced)
        stream.result(timeout=300)
        got = served.logits(who)
    finally:
        eng.close()
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL


def test_whole_and_chunked_prefill_leave_the_same_logits_state_and_rows(
        monkeypatch, reference_weights):
    """One prompt served whole (a bucket of 32) and in chunks of 8 reads the
    same first-token logits and leaves the same rows in the state arena AND in
    the latent arena, to float32 round-off (5e-5 allowed: the chunked scan
    starts from a carried state at another boundary, the latent layer merges
    its softmax over other blocks; 2e-6 read)."""
    from bigdl_tpu.serving import LMServingEngine
    c, prompt = toy(), _ids(29, 9)
    got, rows, real = {}, [], LMServingEngine._pick

    def pick(logits_row, temperature, key, clamp):
        rows.append(np.array(logits_row))
        return real(logits_row, temperature, key, clamp)

    monkeypatch.setattr(LMServingEngine, "_pick", staticmethod(pick))
    for name, kw in (("whole", {"prefill_buckets": [32]}),
                     ("chunked", {"prefill_buckets": [8]})):
        eng = D.build_engine(toy(engine=dict(c["engine"], **kw)), SEED)
        try:
            stream = eng.submit(prompt + 1, max_new_tokens=2)
            stream.result(timeout=300)
            state, tail = (np.asarray(a) for a in eng.state.arenas)
            slot = int(np.argmax(np.abs(state).sum(axis=(0, 2, 3, 4))))
            # the prompt's 29 rows, in chain order (blocks are handed out in
            # ascending order to the engine's first request)
            latent = np.asarray(eng.pool.k)[0, 1:9].reshape(32, -1)[:29, :32]
            got[name] = (rows.pop(0), latent)
            rows.clear()
        finally:
            eng.close()
    want = np.asarray(R.forward(reference_weights, c, prompt))[-1]
    assert np.max(np.abs(got["whole"][0] - want)) < TOL
    for a, b in zip(got["whole"], got["chunked"]):
        assert np.max(np.abs(a)) > 0.1 and np.max(np.abs(a - b)) < 5e-5


def test_a_seated_streams_chain_reads_the_references_rows_from_the_arena():
    """``chain_of`` names where a seated stream's rows lie and ``rows_at``
    reads them as the arena holds them: the prefill's 29 and the decode
    step's are the reference's ``[c ; k_r]`` at their positions to float32
    round-off (5e-5 allowed, 4e-6 read); an ended stream has no chain, and its
    rows stay where they lay (what the cell's check reads by)."""
    c, prompt = toy(), _ids(29, 11)
    eng = D.build_engine(c, SEED)
    try:
        stream = eng.submit(prompt + 1, max_new_tokens=60)
        for i, _ in enumerate(stream.tokens()):
            if i == 5:
                chain = eng.chain_of(stream)
                break
        served = stream.result(timeout=300)[29:] - 1
        assert eng.chain_of(stream) is None
    finally:
        eng.close()
    assert len(chain) >= -(-(29 + 60) // 4) and 0 not in chain
    at = np.arange(29 + 59)                 # the last token's row is never written
    (held,) = eng.pool.rows_at(chain, at)
    assert held.shape == (1, 88, 24 + 8)
    *_, latent = R.forward_requests(
        SEED, c, "float32", [np.concatenate([prompt, served])], latent_at=[at])
    assert latent[0].shape == held.shape and np.max(np.abs(held)) > 0.1
    assert np.max(np.abs(held - np.asarray(latent[0]))) < 5e-5


def test_concurrent_streams_are_the_single_streams(engine):
    prompts = [_ids(n, 10 + n) + 1 for n in (5, 11, 17, 23)]
    alone = [list(engine.submit(p, max_new_tokens=9).result(timeout=300))
             for p in prompts]
    streams = [engine.submit(p, max_new_tokens=9) for p in prompts]
    assert [list(s.result(timeout=300)) for s in streams] == alone


@pytest.mark.parametrize("control", ["state_bf16", "latent_bf16"])
def test_a_lower_precision_cache_fails_the_tolerance(monkeypatch, control,
                                                     reference_weights):
    """The controls: the same serving path with the recurrent state, or the
    latent rows, rounded to bfloat16 where float32 is stated moves the logits
    by more than ``TOL`` (1.3e-3 and 1.5e-3 read)."""
    c, prompt, forced = toy(), _ids(11, 1), _ids(15, 2)
    lower = (toy_solar2.state_rounded("bfloat16") if control == "state_bf16"
             else toy_ling3.latent_rounded("bfloat16"))
    with lower:
        eng = D.build_engine(c, SEED)
        try:
            served = Served(monkeypatch, eng)
            who, stream = served.submit(prompt, forced)
            stream.result(timeout=300)
            got = served.logits(who)
        finally:
            eng.close()
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) > 3 * TOL


# -- (d) the bounded gate ------------------------------------------------------------------
def test_the_bounded_gate_lies_inside_its_bound_and_is_the_reference():
    c = toy()
    model = _model(c)
    w = R.make_layer(SEED, c, 2, "float32")
    bp = D.program_layer(w)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (1, 50, 64))
    _, g, beta, _ = model.kda_inputs(bp, x)
    assert float(jnp.min(g)) > -5.0 and float(jnp.max(g)) < 0.0
    assert float(jnp.min(g)) < -1.0 and float(jnp.max(g)) > -0.01   # across the range
    assert float(jnp.max(beta)) <= 1.0 and float(jnp.min(beta)) >= 0.0   # no factor 2
    a = R._rms(x[0], w["ln1"], c["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        assert float(jnp.max(jnp.abs(g[0] - R.kda_decay(c, w, a)))) < 1e-5


@pytest.mark.parametrize("chunk,sub", [(64, 16), (32, 8)])
def test_a_decay_at_the_bound_on_every_channel_stays_finite(chunk, sub):
    """g = -5 on every channel of every position: a sub-chunk of 16 steps
    forgets by e^-80, inside float32, and the chunked form is the literal
    scan."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    shape = (2, 150, 3, 8)
    q, k = (kda.l2norm(jax.random.normal(kk, shape)) for kk in ks[:2])
    v = jax.random.normal(ks[2], shape)
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[3], shape[:3]))
    state = jax.random.normal(ks[4], (2, 3, 8, 8))
    g = jnp.full(shape, -5.0)
    want_o, want_s = kda.kda_scan(q, k, v, g, beta, state)
    got_o, got_s = kda.kda_chunked(q, k, v, g, beta, state, chunk=chunk, sub=sub)
    assert bool(jnp.all(jnp.isfinite(got_o))) and bool(jnp.all(jnp.isfinite(got_s)))
    assert float(jnp.max(jnp.abs(got_o - want_o))) < KDA_TOL
    assert float(jnp.max(jnp.abs(got_s - want_s))) < KDA_TOL


# -- (e) the routed half: groups ---------------------------------------------------------
def _uncut():
    """The toy's first routed layer with all 16 experts here."""
    c = toy(num_experts=16, expert_share=[0, 1])
    w = R.make_layer(SEED, c, 2, "float32")
    return c, w, D.program_layer(w)["moe"]


def _spec(held, **kw):
    return E.MoESpec(n_experts=16, top_k=4, width=32, shared_width=32,
                     routed_scale=2.5, held=held, score="sigmoid", n_group=4,
                     topk_group=2)._replace(**kw)


def _tokens(n, seed):
    # (channel 0 is the stream's constant, the router's offset: reference_ling3)
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 64)).at[:, 0].set(1.0)


def test_the_picks_are_the_references_five_steps_and_the_groups_matter():
    c, w, p = _uncut()
    m = _tokens(200, 2)
    idx, weight = E.route_top_k(p["router"], m, _spec(None), p["select_bias"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.routing(c, m, w["router"], w["router_bias"]))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(weight), axis=-1)
    assert (got > 0).sum(-1).tolist() == [4] * 200
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    # a token's four experts lie in at most two of the four groups ...
    groups = np.asarray(idx) // 4
    assert max(len(set(row)) for row in groups.tolist()) == 2
    # ... and that moved picks: without the group step some tokens choose others
    free, _ = E.route_top_k(p["router"], m, _spec(None, n_group=1, topk_group=1),
                            p["select_bias"])
    moved = (np.sort(np.asarray(free), -1) != np.sort(np.asarray(idx), -1)).any(-1)
    assert 0.2 < moved.mean() < 1.0
    assert np.allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-5)
    with pytest.raises(ValueError, match="sigmoid router"):
        E.route_top_k(p["router"], m, _spec(None, score="softmax"))


def test_one_group_reproduces_todays_picks_bit_for_bit():
    """``n_group`` 1 and ``topk_group`` 1, the defaults: the selection Laguna
    and Solar compile today (the top-k of score + bias over all experts), to
    the bit, and their layers count no group (two integers and the row tiles)."""
    _, _, p = _uncut()
    m = _tokens(300, 3)
    spec = E.MoESpec(n_experts=16, top_k=3, width=32, score="sigmoid")
    assert (spec.n_group, spec.topk_group, spec.n_counts) == (1, 1, 3)
    idx, weight = E.route_top_k(p["router"], m, spec, p["select_bias"])
    scores = jax.nn.sigmoid(jnp.dot(m, p["router"],
                                    preferred_element_type=jnp.float32))
    _, want = jax.lax.top_k(scores + p["select_bias"], 3)
    picked = jnp.take_along_axis(scores, want, axis=-1)
    assert bool(jnp.all(idx == want))
    assert bool(jnp.all(weight == picked / jnp.sum(picked, -1, keepdims=True)))
    held = dict(p, **{k: p[k][:4] for k in ("w_gate", "w_up", "w_down")})
    assert E.routed_experts(held, m, spec._replace(held=(0, 4)))[1].shape == (3,)
    jaxpr = str(jax.make_jaxpr(lambda x: E.route_top_k(
        p["router"], x, spec, p["select_bias"]))(m))
    assert jaxpr.count("top_k") == 1


def test_the_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The share test: every group's chip computes its own experts' part; the
    parts, with the shared expert counted once, are the uncut layer's result."""
    c, w, p = _uncut()
    m = _tokens(37, 1)
    with jax.default_matmul_precision("highest"):
        routed, shared = R.routed_half(c, w, m)
    whole, counts = E.routed_mlp(p, m, _spec(None))
    assert float(jnp.max(jnp.abs(whole - (routed + shared)))) < 1e-5
    assert int(counts[0]) == 37 * 4 and counts.shape == (4,)
    assert 37 < int(counts[2]) <= 37 * 2            # a token hits one or two groups
    parts, landed = [], 0
    for share in range(4):
        first = 4 * share                           # a group a share
        mine = dict(p, **{k: p[k][first:first + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        y, n = E.routed_experts(mine, m, _spec((first, 4)))
        with jax.default_matmul_precision("highest"):
            ref, _ = R.routed_half(c, dict(w, **{
                k: w[k][first:first + 4] for k in ("e_gate", "e_up", "e_down")}),
                m, experts=(first, 4))
        assert float(jnp.max(jnp.abs(y - ref))) < 1e-5      # share by share
        assert int(n[2]) == int(counts[2])          # the groups hit are the router's
        parts.append(y)
        landed += int(n[0])
    assert landed == 37 * 4                     # every pick lands on one share
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    assert sum(float(jnp.max(jnp.abs(y))) > 0.01 for y in parts) >= 3


def test_the_groups_hit_reach_the_metrics_and_the_trace(engine):
    from bigdl_tpu.obs.tracer import get_tracer
    tracer = get_tracer()
    before = engine.stats()["metrics"]["moe"]
    tracer.enable()
    tracer.clear()
    try:
        engine.submit(_ids(9, 30) + 1, max_new_tokens=6).result(timeout=300)
    finally:
        tracer.disable()
    moe = engine.stats()["metrics"]["moe"]
    rounds = moe["expert_layer_rounds"] - before["expert_layer_rounds"]
    hit = moe["groups_hit"] - before["groups_hit"]
    assert rounds == 5 * 6 and rounds <= hit <= 2 * rounds  # one token, 6 layers
    steps = [e["args"] for e in tracer.events() if e["name"] == "lm/decode_step"]
    assert len(steps) == 5
    assert sum(a["moe_groups_hit"] for a in steps) == hit
    assert [a["latent_positions"] for a in steps] == [9 + i + 1 for i in range(5)]
    assert all(a["state_rows"] == 7 for a in steps)
    assert all(a["state_step_path"] == "xla" for a in steps)


# -- (f) what a cache kind refuses, at construction, one table ------------------------------
def _latent_alone():
    """A model of latent layers alone (no recurrent state), so that the
    latent pool's own rows of the table speak."""
    from bigdl_tpu.models.transformer import (LayerSpec, MLASpec, RopeSpec,
                                              TransformerLM)
    spec = LayerSpec(2, rope=RopeSpec(theta=1e4, rotary_dim=8), mixer="mla")
    return TransformerLM(64, hidden_size=32, n_head=2, n_layers=2, max_len=64,
                         head_dim=16, pos_encoding="none", bias=False,
                         mla=MLASpec(24, 16, 8, 16),
                         layer_plan=[(2, (spec,))]).build(seed=1).evaluate()


def _recurrent():
    c = toy_solar2.config()
    model = serve_solar2.build_model(c)
    model.params = serve_solar2.program_params(model, SEED, c, "float32")
    model.buffers = {}
    return model.evaluate()


@pytest.mark.parametrize("kind,kw,says", [
    ("recurrent", {"spec": 2}, "spec"),
    ("recurrent", {"migrate": lambda *a: None}, "migrate"),
    ("recurrent", {"kvtier": object()}, "kvtier"),
    ("latent", {"kv_quant": "int8"}, "kv_quant='int8'"),
    ("latent", {"spec": "tree"}, "tree verify"),
    ("latent", {"migrate": lambda *a: None}, "migrate"),
    ("latent", {"kvtier": object()}, "kvtier"),
    ("both", {"kv_quant": "int8"}, "kv_quant='int8'"),
    ("both", {"spec": 2}, "spec"),
])
def test_refusals_at_construction(kind, kw, says):
    """One function, one table of (cache kind, feature, why) rows
    (``lm_engine.refuse_unsupported``): a model with recurrent layers, one
    with latent layers, and this configuration, which has both (the recurrent
    rows speak first)."""
    from bigdl_tpu.serving import LMServingEngine
    from bigdl_tpu.serving.spec import SpecConfig
    if kw.get("spec") == "tree":    # (a latent pool serves a chain verify)
        kw = {"spec": SpecConfig(k=2, tree=True, drafter_compute="ngram")}
    model = {"recurrent": _recurrent, "latent": _latent_alone,
             "both": lambda: _model(toy())}[kind]()
    names = {"recurrent": ("recurrent layers", "M6"),
             "latent": ("latent attention layers", "M4")}
    name, milestone = names["latent" if "kv_quant" in kw or kind == "latent"
                            else "recurrent"]
    with pytest.raises(ValueError, match=name + " cannot serve with "
                       ".*" + says) as e:
        LMServingEngine(model, slots=2, block_len=4, cache_len=64,
                        prefill_buckets=(8,), num_blocks=40, **kw)
    assert milestone in str(e.value)


def test_every_refusal_is_a_row_of_the_one_table():
    from bigdl_tpu.serving import lm_engine
    rows = lm_engine._REFUSALS
    assert len(rows) == len({r[:2] for r in rows}) == 21
    assert {r[0] for r in rows} == set(lm_engine._KIND_NAMES)
    lm_engine.refuse_unsupported(_latent_alone())           # nothing given: silent


def test_a_model_with_recurrent_layers_serves_through_the_block_table_kernel():
    """What the table refused until the kernel read shared K/V heads
    (``ops.grouped_attention``): the engine builds, keeps the kernel and
    decodes what the model scores best."""
    from bigdl_tpu.serving import LMServingEngine
    model = _recurrent()
    eng = LMServingEngine(model, slots=2, block_len=4, cache_len=64,
                          prefill_buckets=(8,), num_blocks=40,
                          decode_attn="paged_kernel")
    try:
        assert eng.decode_attn == eng.stats()["decode_attn"] == "paged_kernel"
        assert eng.state is not None and len(eng._arenas()) == 4
        prompt = _ids(13, 3) % 64 + 1
        out = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        logp = np.asarray(model.f(model.params, jnp.asarray(out[None])))[0]
        assert (logp[12:-1].argmax(-1) + 1 == out[13:]).all()   # greedy, teacher-forced
    finally:
        eng.close()


def test_a_model_of_latent_layers_alone_serves_and_refuses_adoption():
    from bigdl_tpu.serving import LMServingEngine
    model = _latent_alone()
    eng = LMServingEngine(model, slots=2, block_len=4, cache_len=64,
                          prefill_buckets=(8,), num_blocks=40)
    try:
        assert eng.state is None and eng.pool.latent and len(eng._arenas()) == 1
        prompt = _ids(13, 3) % 64 + 1
        out = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        logp = np.asarray(model.f(model.params, jnp.asarray(out[None])))[0]
        assert (logp[12:-1].argmax(-1) + 1 == out[13:]).all()   # greedy, teacher-forced
        with pytest.raises(ValueError, match="latent attention layers cannot adopt"):
            eng.adopt(object())
    finally:
        eng.close()


def test_a_plan_that_mixes_attention_and_latent_layers_is_not_served():
    from bigdl_tpu.models.transformer import (LayerSpec, MLASpec, RopeSpec,
                                              TransformerLM)
    from bigdl_tpu.serving import LMServingEngine
    rope = RopeSpec(theta=1e4, rotary_dim=8)
    model = TransformerLM(64, hidden_size=32, n_head=2, n_layers=2, max_len=64,
                          head_dim=16, pos_encoding="none", bias=False,
                          mla=MLASpec(24, 16, 8, 16),
                          layer_plan=[(1, (LayerSpec(2), LayerSpec(2, rope=rope,
                                                                   mixer="mla")))])
    model.build(seed=1).evaluate()
    y = model.f(model.params, jnp.asarray(_ids(10)[None] % 64 + 1))
    assert bool(jnp.all(jnp.isfinite(y)))                   # the forward runs it
    with pytest.raises(ValueError, match="one pool holds one kind of row"):
        LMServingEngine(model, slots=2, block_len=4, cache_len=64,
                        prefill_buckets=(8,), num_blocks=40)


# -- (g) the pool alone -----------------------------------------------------------------------
def test_a_latent_pool_is_one_arena_and_refuses_what_carries_pairs():
    from bigdl_tpu.serving.kvcache.blocks import BlockPool, list_chunk
    pool = BlockPool(n_layers=1, n_heads=1, head_dim=576, block_len=16,
                     num_blocks=9, dtype=jnp.bfloat16, latent=True)
    assert pool.shape == (1, 9, 16, 640) and pool.arenas == (pool.k,)
    assert pool.wire_shape == (1, 1, 16, 576) and pool.block_bytes == 16 * 576 * 2
    assert pool.row_bytes == 1280 and pool.arena_bytes == 9 * 16 * 1280
    pool.arenas = (pool.k + 1,)
    assert float(pool.k[0, 0, 0, 0]) == 1.0
    pair = BlockPool(n_layers=1, n_heads=2, head_dim=64, block_len=16,
                     num_blocks=9, dtype=jnp.bfloat16)
    assert pair.row_bytes == 2 * 128 * 2 and pair.data_arenas == 2
    for bad in ({"kv_quant": "int8"}, {"n_heads": 2}):
        with pytest.raises(ValueError, match="latent pool"):
            BlockPool(**dict(dict(n_layers=1, n_heads=1, head_dim=576, block_len=16,
                                  num_blocks=9, latent=True), **bad))
    for call in (lambda: pool.export_chain([1]),
                 lambda: pool.adopt_chain(np.zeros(1), np.zeros(1)),
                 lambda: pool.warmup_adopt([1])):
        with pytest.raises(NotImplementedError, match="M4"):
            call()
    # a latent walk takes forty blocks a slot (the chip's sweep: PERF.md, PR 35);
    # grouped heads and verify steps keep sixteen, one query vector a head four
    assert list_chunk(32, latent=True) == list_chunk(32, True, latent=True) == 40 * 32
    assert list_chunk(32, True) == list_chunk(128, True) // 4 == 512
    assert list_chunk(16, False) == 64
