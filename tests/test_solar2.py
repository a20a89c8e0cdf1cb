"""Solar-Open2-250B's block through ``TransformerLM`` and ``LMServingEngine``
at a toy size, against the plain reference the benchmark keeps
(``benchmarks/harness/reference_solar2.py``, the KDA layer a literal scan over
positions): hidden 64, softmax layers of 8 query heads of 16 over 2 K/V heads
with no position encoding and an elementwise gate, KDA layers of 4 heads with
a 16 x 16 state and a convolution of 4, 16 sigmoid-routed experts top-3 of
which 2 are held, a shared expert; two whole periods (softmax, KDA, KDA, KDA).

LOGITS are compared, not tokens.  Tolerances, each with its reason:

- ``TOL`` 2e-4 on logits of size 5: both sides compute in float32 on the CPU
  (the program at XLA's default, full float32 there, its recurrence at
  ``highest``; the reference at ``highest``) and differ by the order of their
  sums, 8e-7 to 3.4e-6 read on this toy (the whole forward, a bucket-padded
  prefill and its decode rounds, a prompt prefilled in chunks); 2e-4 leaves
  sixty times that and is a twenty-fifth of what a state kept in bfloat16
  moves (5.4e-3: ``test_a_state_kept_in_bfloat16_fails_the_tolerance``).
- ``KDA_TOL`` 2e-5 on outputs and states of size 1: the chunked form, the
  folded step and the literal scan are three orders of the same float32 sums
  (4e-7 to 4e-6 read, the largest at a decay of 1e-6 a step over chunks of 64).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_solar2 as D
from benchmarks.harness import reference_solar2 as R
from benchmarks.tests import toy_solar2
from benchmarks.tests.served import Served as _Served
from bigdl_tpu.nn import kda
from bigdl_tpu.parallel import expert as E

TOL = 2e-4
KDA_TOL = 2e-5
SEED = 5

toy = toy_solar2.config


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 96, size=(n,)).astype(np.int32)


# -- (a) the recurrence: chunked = the literal scan = the step folded ----------------
def _kda_inputs(seed, b=2, t=150, h=3, dk=8, dv=8, slowest=1e-3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, h, dk)))
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    # the decay a step log-uniform down to ``slowest`` (exp(g) = 1e-3: a
    # channel that forgets everything in two steps)
    g = jax.random.uniform(ks[3], (b, t, h, dk), minval=np.log(slowest), maxval=0.0)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, t, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    ragged = jax.random.uniform(ks[6], (b, t)) > 0.3
    return (q, k, v, g, beta), state, ragged


def _reference_scan(x, state, valid):
    """benchmarks' literal recurrence, a sequence at a time."""
    outs, states = [], []
    for i in range(x[0].shape[0]):
        row = [a[i] for a in x]
        if valid is not None:
            row[3] = jnp.where(valid[i][:, None, None], row[3], 0.0)
            row[4] = jnp.where(valid[i][:, None], row[4], 0.0)
        o, s = R.kda_recurrence(*row, None if state is None else state[i])
        outs.append(o)
        states.append(s)
    return jnp.stack(outs), jnp.stack(states)


@pytest.mark.parametrize("chunk,sub", [(64, 16), (32, 8), (16, 16), (128, 16)])
@pytest.mark.parametrize("case", ["plain", "initial_state", "ragged_mask",
                                  "strong_decay", "all_three"])
def test_chunked_is_the_literal_scan_is_the_folded_step(chunk, sub, case):
    x, state, ragged = _kda_inputs(
        3, slowest=1e-3 if case in ("strong_decay", "all_three") else 0.5)
    state = state if case in ("initial_state", "all_three") else None
    valid = ragged if case in ("ragged_mask", "all_three") else None
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _reference_scan(x, state, valid)
    got_o, got_s = jax.jit(lambda *a: kda.kda_chunked(
        *a, chunk=chunk, sub=sub))(*x, state, valid)
    fold_o, fold_s = kda.kda_scan(*x, state, valid)
    seen = jnp.ones(x[0].shape[:2], bool) if valid is None else valid
    for o, s in ((got_o, got_s), (fold_o, fold_s)):
        assert bool(jnp.all(jnp.isfinite(o)))
        assert float(jnp.max(jnp.abs(jnp.where(seen[..., None, None],
                                               o - want_o, 0.0)))) < KDA_TOL
        assert float(jnp.max(jnp.abs(s - want_s))) < KDA_TOL


def test_a_decay_that_would_overflow_the_usual_factoring_stays_finite():
    """exp(g) = 1e-6 a step: e^{-G} passes float32's largest after 7 steps."""
    x, state, _ = _kda_inputs(4, t=70, slowest=1e-6)
    x = x[:3] + (jnp.full_like(x[3], np.log(1e-6)),) + x[4:]
    want_o, want_s = kda.kda_scan(*x, state)
    got_o, got_s = kda.kda_chunked(*x, state)
    assert bool(jnp.all(jnp.isfinite(got_o))) and bool(jnp.all(jnp.isfinite(got_s)))
    assert float(jnp.max(jnp.abs(got_o - want_o))) < KDA_TOL
    assert float(jnp.max(jnp.abs(got_s - want_s))) < KDA_TOL


def test_an_invalid_position_leaves_the_state_as_it_was():
    x, state, _ = _kda_inputs(5, t=40)
    none = jnp.zeros(x[0].shape[:2], bool)
    assert bool(jnp.all(kda.kda_chunked(*x, state, none)[1] == state))
    o, s = kda.kda_step(*(a[:, 0] for a in x[:3]), jnp.zeros_like(x[3][:, 0]),
                        jnp.zeros_like(x[4][:, 0]), state)
    assert bool(jnp.all(s == state))


@pytest.mark.parametrize("lengths", [[13, 13], [13, 2], [0, 7]])
def test_the_convolution_hands_out_the_tail_of_the_true_end(lengths):
    """Whole rows with bucket padding past each row's length = the step
    folded over the real positions alone; the tail is that of the last three
    REAL inputs (reaching into the incoming tail where fewer than three)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (2, 13, 10))
    w = jax.random.normal(ks[1], (4, 10))
    tail = jax.random.normal(ks[2], (2, 3, 10))
    y, new = kda.short_conv(x, w, tail, jnp.asarray(lengths))
    for row, n in enumerate(lengths):
        t = tail[row]
        for i in range(n):
            yi, t = kda.short_conv_step(x[row, i], w, t)
            assert float(jnp.max(jnp.abs(yi - y[row, i]))) < 1e-6
        assert bool(jnp.all(t == new[row]))
    assert bool(jnp.all(kda.short_conv(x, w, tail)[1] == x[:, -3:]))


# -- (b) the whole forward ----------------------------------------------------------
@pytest.fixture(scope="module")
def reference_weights():
    return R.make_weights(SEED, toy(), "float32")


def _model(c):
    model = D.build_model(c)
    model.params = D.program_params(model, SEED, c, "float32")
    model.buffers = {}
    return model.evaluate()


def test_layer_plan_is_whole_periods_of_one_softmax_and_three_kda_layers():
    model = D.build_model(toy())
    (repeat, period), = model.plan
    assert repeat == 2 and [s.mixer for s in period] == ["attention"] + ["kda"] * 3
    assert [s.n_head for s in period] == [8, 4, 4, 4]
    assert model.kv_layers == (0, 4) and model.state_layers == (1, 2, 3, 5, 6, 7)
    assert model.state_shapes == ((4, 16, 16), (3, 3 * 4 * 16))
    assert model.pos_encoding == "none" and model.attn_gate == "elementwise"
    assert "pos" not in model.init(jax.random.PRNGKey(0))
    assert model.moe.score == "sigmoid" and model.moe_layers == 8


@pytest.mark.parametrize("n", [45, 64, 7])
def test_full_forward_matches_the_reference(reference_weights, n):
    """The training-side forward: under, at and over a chunk of the scan."""
    c = toy()
    model, ids = _model(c), _ids(n)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) < TOL


def test_the_built_model_initialises_and_runs():
    """``build()`` as any user's model: the generic initialiser's KDA block."""
    model = D.build_model(toy()).build(seed=3)
    y = model.f(model.params, jnp.asarray(_ids(20)[None] + 1))
    assert y.shape == (1, 20, 96) and bool(jnp.all(jnp.isfinite(y)))
    kp = model.params["groups"][0][1]["kda"]
    assert kp["conv"].shape == (2, 4, 192) and kp["a_log"].shape == (2, 4)


# -- (c) served: prefill, then decoding through the state arena and the pool ----------
def _want(weights, c, prompt, forced):
    ids = np.concatenate([prompt, forced])
    t = len(prompt)
    return np.asarray(R.forward(weights, c, ids))[t - 1:t - 1 + len(forced)]


@pytest.fixture(scope="module")
def engine():
    eng = D.build_engine(toy(), SEED)
    yield eng
    eng.close()


def test_the_pool_holds_the_attention_layers_and_the_arena_the_rest(engine):
    assert engine.pool.n_layers == 2 and engine.pool.shape[0] == 2
    assert engine.state.state.shape == (6, 4, 4, 16, 16)
    assert engine.state.state.dtype == jnp.float32
    assert engine.state.tail.shape == (6, 4, 3, 192)
    assert engine.radix is None and engine.decode_attn == "gather"
    stats = engine.stats()
    assert "recurrent" in stats["prefix_cache"] and "M6" in stats["prefix_cache"]
    assert stats["state"]["layers"] == 6
    assert stats["state"]["bytes"] == engine.state.arena_bytes == 6 * 4 * (
        4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["kvcache"]["prefix_cache"] is None


def test_prefill_then_decode_through_the_arena_matches_the_reference(
        monkeypatch, engine, reference_weights):
    """Through ``LMServingEngine.submit``: a prompt of 19 (bucket 32: thirteen
    padded positions the state must not see), then 14 decode rounds with
    three idle slots beside it."""
    c, prompt, forced = toy(), _ids(19, 1), _ids(15, 2)
    before = engine.stats()["metrics"]["state"]["row_steps"]
    served = _Served(monkeypatch, engine)
    who, stream = served.submit(prompt, forced)
    stream.result(timeout=300)
    got = served.logits(who)
    assert got.shape == (15, 96)
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL
    state = engine.stats()["metrics"]["state"]
    assert state["row_steps"] - before == 14 * 6     # one slot, six layers
    assert state["rows_in_use"] == 0                 # and it has finished


def test_mixed_rounds_idle_slots_and_a_reused_slot(monkeypatch, engine,
                                                   reference_weights):
    """Six requests over four slots: rounds of four, three, two and one
    active slots, and two requests seated into slots that another has just
    left (their rows are written anew at admission, whatever was there)."""
    c = toy()
    served = _Served(monkeypatch, engine)
    jobs = [(_ids(n, 20 + i), _ids(m, 40 + i))
            for i, (n, m) in enumerate([(5, 4), (30, 9), (12, 13), (8, 6),
                                        (17, 7), (3, 11)])]
    # told apart by their first token
    jobs = [(np.concatenate([[i], p[1:]]).astype(np.int32), f)
            for i, (p, f) in enumerate(jobs)]
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


def test_a_prompt_over_the_largest_bucket_prefills_in_chunks(
        monkeypatch, engine, reference_weights):
    """45 tokens over buckets of at most 32: a chunk of 32 whose state is
    written to the slot's rows, then a suffix of 13 (bucket 16) that starts
    from them, over the cached K/V of the two softmax layers."""
    c, prompt, forced = toy(), _ids(45, 7), _ids(8, 8)
    misses = engine.stats()["prefix_prefill_cache"]["misses"]
    served = _Served(monkeypatch, engine)
    who, stream = served.submit(prompt, forced)
    stream.result(timeout=300)
    assert engine.stats()["prefix_prefill_cache"]["misses"] == misses + 1
    got = served.logits(who)
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL


def test_whole_and_chunked_prefill_leave_the_same_logits_and_state(
        monkeypatch, reference_weights):
    """``max_prefill_chunk_tokens``: one prompt served whole (bucket 32) and
    in chunks of 8 between decode rounds reads the same first-token logits
    and leaves the same rows in the state arena, to float32 round-off (the
    chunked scan starts from a carried state at another boundary: 1e-6 read,
    5e-5 allowed, under a fiftieth of what bfloat16 rounding would move)."""
    from bigdl_tpu.serving import LMServingEngine
    c, prompt = toy(), _ids(29, 9)
    got, rows, real = {}, [], LMServingEngine._pick

    def pick(logits_row, temperature, key, clamp):
        rows.append(np.array(logits_row))
        return real(logits_row, temperature, key, clamp)

    monkeypatch.setattr(LMServingEngine, "_pick", staticmethod(pick))
    for name, kw in (("whole", {}), ("chunked", {"max_prefill_chunk_tokens": 8})):
        eng = D.build_engine(toy(engine=dict(c["engine"], **kw)), SEED)
        try:
            eng.submit(prompt + 1, max_new_tokens=1).result(timeout=300)
            state, tail = (np.asarray(a) for a in eng.state.arenas)
            slot = int(np.argmax(np.abs(state).sum(axis=(0, 2, 3, 4))))
            got[name] = (rows.pop(), state[:, slot], tail[:, slot])
        finally:
            eng.close()
    want = np.asarray(R.forward(reference_weights, c, prompt))[-1]
    assert np.max(np.abs(got["whole"][0] - want)) < TOL
    for a, b in zip(got["whole"], got["chunked"]):
        assert np.max(np.abs(a)) > 0.1 and np.max(np.abs(a - b)) < 5e-5


def test_concurrent_streams_are_the_single_streams(engine):
    prompts = [_ids(n, 10 + n) + 1 for n in (5, 11, 17, 23)]
    alone = [list(engine.submit(p, max_new_tokens=9).result(timeout=300))
             for p in prompts]
    streams = [engine.submit(p, max_new_tokens=9) for p in prompts]
    assert [list(s.result(timeout=300)) for s in streams] == alone


def test_a_state_kept_in_bfloat16_fails_the_tolerance(monkeypatch,
                                                      reference_weights):
    """The control: the same serving path with the recurrent state rounded to
    bfloat16 around ``kda_step`` / ``kda_chunked`` moves the logits by more
    than ``TOL`` (5.4e-3 read: twenty-five times it)."""
    c, prompt, forced = toy(), _ids(19, 1), _ids(15, 2)
    with toy_solar2.state_rounded("bfloat16"):
        eng = D.build_engine(c, SEED)
        try:
            served = _Served(monkeypatch, eng)
            who, stream = served.submit(prompt, forced)
            stream.result(timeout=300)
            got = served.logits(who)
        finally:
            eng.close()
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) > 5 * TOL


def _eight_heads(head_dim=16):
    """The toy with KDA layers of 8 heads: one head block of ``ops.kda_step``."""
    c = toy()
    return toy(head_dim=head_dim, linear_attn_config=dict(
        c["linear_attn_config"], num_heads=8, head_dim=head_dim))


def test_the_engine_says_which_form_its_recurrence_takes(monkeypatch, engine):
    """``stats()["state"]["step_path"]``, resolved once at construction by
    ``ops.kda_step.kda_step_path`` -- the platform and the state's shape: the
    CPU takes ``kda_step``; seen as a TPU (``use_interpret`` steered false,
    as ``tests/test_chip_compile.py`` steers it) a state of 8 heads of 128 x
    128 the kernel, and the toy's 4 heads of 16 x 16 still ``kda_step``."""
    from bigdl_tpu.ops import _pallas
    assert engine.stats()["state"]["step_path"] == "xla"
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    for c, path in ((_eight_heads(128), "kernel"), (toy(), "xla")):
        eng = D.build_engine(c, SEED)
        try:
            assert eng.stats()["state"]["step_path"] == path
        finally:
            eng.close()


def test_tokens_through_the_interpreted_kernel_are_the_xla_paths(monkeypatch):
    """A short mixed run -- four streams of 3 to 14 tokens over four slots, so
    slots go idle mid-way while the others decode on -- served twice: through
    ``kda_step`` and with the rule stood in for (``kda_step_path`` is the
    seam; on the CPU the kernel is interpreted).  The same tokens, and the
    kernel ran a call a recurrent layer a traced step."""
    from bigdl_tpu.ops import kda_step as K
    prompts = [_ids(n, 30 + n) + 1 for n in (5, 9, 14, 7)]
    lengths = (3, 14, 6, 10)
    calls, real = [], K.kda_step_rows

    def counted(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)

    def serve():
        eng = D.build_engine(_eight_heads(), SEED)
        try:
            streams = [eng.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, lengths)]
            tokens = [list(s.result(timeout=300)) for s in streams]
            return tokens, np.asarray(eng.state.state)
        finally:
            eng.close()

    want, want_state = serve()
    assert not calls
    monkeypatch.setattr(K, "kda_step_rows", counted)
    monkeypatch.setattr(K, "kda_step_path", lambda *shape: "kernel")
    got, got_state = serve()
    assert [len(t) - len(p) for t, p in zip(got, prompts)] == list(lengths)
    assert got == want
    assert calls and set(calls) == {(6, 4, 8, 16, 16)}
    # the arenas the two runs leave: the same rows to float32 round-off
    assert np.max(np.abs(got_state - want_state)) < KDA_TOL * max(
        1.0, float(np.max(np.abs(want_state))))


# -- (d), (e) the routed half ---------------------------------------------------------
def _uncut():
    """The toy's first layer with all 16 experts here."""
    c = toy(n_routed_experts=16, expert_share=[0, 1])
    w = R.make_layer(SEED, c, 0, "float32")
    return c, w, D.program_layer(w)["moe"]


def _spec(held, **kw):
    return E.MoESpec(n_experts=16, top_k=3, width=32, shared_width=32,
                     held=held, score="sigmoid")._replace(**kw)


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    c, w, p = _uncut()
    m = jax.random.normal(jax.random.PRNGKey(1), (37, 64)).at[:, 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        routed, shared = R.routed_half(c, w, m)
    whole, counts = E.routed_mlp(p, m, _spec(None))
    assert float(jnp.max(jnp.abs(whole - (routed + shared)))) < 1e-5
    assert int(counts[0]) == 37 * 3
    parts, landed = [], 0
    for share in range(8):
        first = 2 * share
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        y, n = E.routed_experts(mine, m, _spec((first, 2)))
        with jax.default_matmul_precision("highest"):
            ref, _ = R.routed_half(c, dict(w, **{
                k: w[k][first:first + 2] for k in ("e_gate", "e_up", "e_down")}),
                m, experts=(first, 2))
        assert float(jnp.max(jnp.abs(y - ref))) < 1e-5      # share by share
        parts.append(y)
        landed += int(n[0])
    assert landed == 37 * 3                     # every pick lands on one share
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    assert sum(float(jnp.max(jnp.abs(y))) > 0.01 for y in parts) >= 6


def test_the_sigmoid_router_picks_by_score_plus_bias_and_weighs_by_score():
    c, w, p = _uncut()
    # (channel 0 is the stream's constant, the router's offset: reference_solar2)
    m = jax.random.normal(jax.random.PRNGKey(2), (50, 64)).at[:, 0].set(1.0)
    scores = np.asarray(jax.nn.sigmoid(m @ p["router"]))
    # a bias that lifts expert 11 over everyone and sinks expert 3
    bias = np.zeros((16,), np.float32)
    bias[11], bias[3] = 2.0, -2.0
    idx, weight = E.route_top_k(p["router"], m, _spec(None), jnp.asarray(bias))
    idx, weight = np.asarray(idx), np.asarray(weight)
    want = np.argsort(-(scores + bias), axis=-1)[:, :3]
    assert (np.sort(idx, -1) == np.sort(want, -1)).all()
    assert (idx == 11).any(axis=-1).all() and not (idx == 3).any()
    unbiased = np.argsort(-scores, axis=-1)[:, :3]
    assert (np.sort(unbiased, -1) != np.sort(want, -1)).any()   # the bias moved picks
    picked = np.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(weight, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)                       # the bias weighs nothing
    assert np.asarray(E.route_top_k(
        p["router"], m, _spec(None, norm_topk=False, routed_scale=2.0),
        jnp.asarray(bias))[1]) == pytest.approx(2.0 * picked, rel=1e-6)
    with pytest.raises(ValueError, match="score"):
        E.route_top_k(p["router"], m, _spec(None)._replace(score="tanh"))


def test_a_softmax_router_has_no_bias_and_routes_as_before():
    spec = E.MoESpec(n_experts=16, top_k=3, width=32)
    p = E.init_routed_params(jax.random.PRNGKey(0), spec, 64)
    assert "select_bias" not in p and spec.score == "softmax"
    assert "select_bias" in E.init_routed_params(
        jax.random.PRNGKey(0), spec._replace(score="sigmoid"), 64)
    m = jax.random.normal(jax.random.PRNGKey(3), (9, 64))
    idx, w = E.route_top_k(p["router"], m, spec)
    probs = jax.nn.softmax(m @ p["router"], axis=-1)
    top, want = jax.lax.top_k(probs, 3)
    assert bool(jnp.all(idx == want))
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True), rtol=1e-6)


# -- (f) what a model with recurrent layers refuses (tests/test_ling3.py holds the
# -- refusals at construction, every cache kind's, as one table), and what it does not
def test_adopting_a_migrated_request_is_refused(engine):
    with pytest.raises(ValueError, match="recurrent layers cannot adopt"):
        engine.adopt(object())


def test_int8_kv_stays_legal_and_touches_the_softmax_layers_alone():
    eng = D.build_engine(toy(engine=dict(toy()["engine"], kv_quant="int8")), SEED)
    try:
        assert eng.pool.k.dtype == jnp.int8 and len(eng._arenas()) == 6
        assert eng.state.state.dtype == jnp.float32
        out = eng.submit(_ids(11, 3) + 1, max_new_tokens=5).result(timeout=300)
        assert len(out) == 16
    finally:
        eng.close()


def test_a_model_without_recurrent_layers_has_no_arena_and_says_so():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    model = TransformerLM(64, hidden_size=32, n_head=2, n_layers=2,
                          max_len=32).build(seed=1).evaluate()
    assert model.kv_layers == (0, 1) and model.state_layers == ()
    eng = LMServingEngine(model, slots=2, block_len=4, cache_len=32,
                          prefill_buckets=(8,), enable_prefix_cache=False)
    try:
        assert eng.state is None and eng.stats()["state"] is None
        assert eng.stats()["prefix_cache"] == "off: enable_prefix_cache=False"
        assert len(eng._arenas()) == 2
    finally:
        eng.close()


@pytest.mark.parametrize("bad", [{"pos_encoding": "alibi"}, {"attn_gate": "rowwise"}])
def test_the_constructor_names_what_it_accepts(bad):
    from bigdl_tpu.models.transformer import LayerSpec, TransformerLM
    with pytest.raises(ValueError, match="must be"):
        TransformerLM(64, hidden_size=32, n_head=2, n_layers=1, max_len=32,
                      layer_plan=[(1, (LayerSpec(2),))], **bad)
    with pytest.raises(ValueError, match="mixer"):
        TransformerLM(64, hidden_size=32, n_head=2, n_layers=1, max_len=32,
                      layer_plan=[(1, (LayerSpec(2, mixer="mamba"),))])
