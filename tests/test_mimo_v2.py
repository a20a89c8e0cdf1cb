"""MiMo-V2-Flash at toy size on the CPU (``benchmarks/tests/toy_mimo_v2.py``):
the program against its plain reference (``benchmarks/harness/
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


# -- the layers ----------------------------------------------------------------
def test_the_configuration_builds_two_classes_of_blocks():
    model = D.build_model(toy())
    full, sliding = model.cache_classes
    assert (full.n_kv, full.k_dim, full.v_dim, full.window) == (1, 24, 16, None)
    assert (sliding.n_kv, sliding.window) == (2, WINDOW)
    assert full.layers == (0, 5) and sliding.layers == (1, 2, 3, 4, 6)
    assert [(r, len(p)) for r, p in model.plan] == [(1, 1), (1, 6)]
    # rotary over the first int(24 * 0.334) = 8 lanes, by kind of layer
    specs = [s for _, period in model.plan for s in period]
    assert {(s.rope.theta, s.rope.rotary_dim, s.sink, s.n_kv_head)
            for s in specs} == {(5e6, 8, False, 1), (1e4, 8, True, 2)}


def test_full_forward_matches_the_reference(reference_weights):
    """Whole-sequence logits, a sequence of several windows."""
    c = toy()
    model, ids = _model(c), _ids(37)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) < TOL


def test_the_sinks_matter(reference_weights):
    """On a sliding layer the sink takes a tenth to a half of a row's
    probability at the configuration's spreads (here: that leaving it out
    moves the logits far outside the tolerance)."""
    c = toy()
    model, ids = _model(c), _ids(37)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    with toy_mimo_v2.sink_dropped():
        got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) > 1000 * TOL


# -- through the pool ------------------------------------------------------------
def test_prefill_then_decode_past_several_windows(monkeypatch, engine,
                                                  reference_weights):
    """A prompt of 19 (past two windows), then 30 decode rounds: the windowed
    class lets go of blocks all the way, and the logits stay the reference's
    full forward's."""
    c, prompt, forced = toy(), _ids(19, 1), _ids(31, 2)
    assert engine.decode_attn == "gather"
    assert [k["window"] for k in engine.stats()["kv_classes"]] == [None, WINDOW]
    before = engine.metrics.window_blocks_released
    got, = _serve(monkeypatch, engine, [(prompt, forced)])
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL
    m = engine.metrics
    assert m.window_blocks_released - before >= (19 + 30 - WINDOW) // B - 1
    assert 0 < m.window_blocks_held_max <= KB.window_blocks(WINDOW, B)
    assert m.decode_window_tokens < m.decode_context_tokens


def test_chunked_prefill_of_a_long_prompt(monkeypatch, engine, reference_weights):
    """A prompt of 70 in chunks of 32, 32 and 6: the later chunks' suffix
    prefills read the full class's whole prefix and the windowed class's
    window, of which the earlier chunks' blocks are let go as it advances."""
    c, prompt, forced = toy(), _ids(70, 3), _ids(9, 4)
    got, = _serve(monkeypatch, engine, [(prompt, forced)])
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL


def test_the_prefix_is_walked_a_step_at_a_time(monkeypatch, reference_weights):
    """Steps of 16 positions: a suffix prefill's walk over a prefix of 64."""
    from bigdl_tpu.models.transformer import generate as G
    monkeypatch.setattr(G, "LATENT_PREFIX_STEP", 16)
    monkeypatch.setattr(G, "DENSE_PREFIX_MAX", 16)
    c, prompt, forced = toy(), _ids(70, 5), _ids(3, 6)
    eng = D.build_engine(c, SEED)
    try:
        assert eng._prefix_block_buckets == (eng.table_width,)
        got, = _serve(monkeypatch, eng, [(prompt, forced)])
    finally:
        eng.close()
    assert np.max(np.abs(got - _want(reference_weights, c, prompt, forced))) < TOL


def test_a_radix_hit_beside_a_live_donor_that_let_go(monkeypatch, engine,
                                                     reference_weights):
    """The donor (a prompt of 24, still decoding, its own references behind
    the window released) and a second request that shares its first 20 tokens:
    the hit hands out the trie's blocks of BOTH classes, and its logits are a
    cold prefill's -- the reference's.  (The donor decodes greedily beside it:
    the stand-in for the decode executable hands it its own argmax.)"""
    import time
    from bigdl_tpu.models.transformer import generate as G
    from bigdl_tpu.serving import lm_engine
    c, first = toy(), _ids(24, 7)
    second, forced = np.concatenate([first[:20], _ids(9, 8)]), _ids(6, 10)
    hits = engine.radix.stats()["hits"]
    released = engine.metrics.window_blocks_released
    donor = engine.submit(first + 1, max_new_tokens=60)
    while len(donor._tokens) < 14:
        time.sleep(0.01)
    assert engine.metrics.window_blocks_released - released >= 5
    rows, queue = [], list(forced)
    step = jax.jit(lambda p, token, pos, live, *kv: G._decode_step_paged(
        engine.model, p, token, pos, live, *kv, table_width=engine.table_width,
        attn_impl=engine.decode_attn))

    def pick(logits_row, temperature, key, clamp):
        rows.append(np.array(logits_row))
        return int(queue.pop(0))

    def decode(params, operands, prev_ids, *kv):
        token, pos, _, _, live = lm_engine.split_decode_operands(
            jnp.asarray(operands), engine.slots)
        token = jnp.where(token < 0, prev_ids, token)
        logits, *rest = step(params, token, pos, live, *kv)
        ids = np.array(jnp.argmax(logits, -1), np.int32)
        for i, st in enumerate(engine._slots):
            if st is not None and st.stream is not donor and queue:
                rows.append(np.array(logits[i]))
                ids[i] = queue.pop(0)
        return (jnp.asarray(ids), *rest)

    with monkeypatch.context() as patch:
        patch.setattr(lm_engine.LMServingEngine, "_pick", staticmethod(pick))
        patch.setattr(engine, "_decode_exec", decode)
        engine.submit(second + 1, max_new_tokens=len(forced)).result(timeout=300)
    assert not donor.done()
    donor.cancel()
    assert engine.radix.stats()["hits"] == hits + 1
    assert np.max(np.abs(np.stack(rows) - _want(reference_weights, c, second,
                                                forced))) < TOL


def test_concurrent_streams_are_the_single_streams(engine):
    prompts = [_ids(n, 10 + n) + 1 for n in (5, 11, 17, 41)]
    alone = [list(engine.submit(p, max_new_tokens=14).result(timeout=300))
             for p in prompts]
    streams = [engine.submit(p, max_new_tokens=14) for p in prompts]
    assert [list(s.result(timeout=300)) for s in streams] == alone
    # nothing is held once the streams have ended but what the trie keeps
    for i, k in enumerate(engine.stats()["kv_classes"]):
        assert k["used_blocks"] <= engine.radix.stats()["nodes"], i


# -- the expert layer ------------------------------------------------------------
def test_the_sixteenth_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts: the routed parts of all shares add up to
    the uncut layer's output (no shared expert, no scale)."""
    c = toy()
    w = R.make_layer(SEED, c, 1, "float32")
    m = jax.random.normal(jax.random.PRNGKey(1), (37, 64))
    shares = [R.make_experts(SEED, c, 1, "float32", s) for s in range(8)]
    with jax.default_matmul_precision("highest"):
        weights = R.routing(c, m, w["router"], w["router_bias"])
        whole = sum(R.routed_part(c, m, weights[:, 2 * s:2 * s + 2], shares[s])
                    for s in range(8))
    assert np.allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    parts, landed = [], 0
    for s in range(8):
        spec = E.MoESpec(n_experts=16, top_k=4, width=32, held=(2 * s, 2),
                         score="sigmoid")
        p = {"router": w["router"], "select_bias": w["router_bias"],
             "w_gate": shares[s]["e_gate"], "w_up": shares[s]["e_up"],
             "w_down": shares[s]["e_down"]}
        y, n = E.routed_experts(p, m, spec)
        parts.append(y)
        landed += int(n[0])
    assert landed == 37 * 4                     # every pick lands on one share
    assert float(jnp.max(jnp.abs(sum(parts) - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.01


# -- spans and counters -------------------------------------------------------------
def test_the_round_spans_and_the_classes_stats(engine):
    from bigdl_tpu.obs.tracer import get_tracer
    tracer = get_tracer()
    tracer.enable()
    tracer.clear()
    try:
        engine.submit(_ids(21, 20) + 1, max_new_tokens=12).result(timeout=300)
    finally:
        tracer.disable()
    events = tracer.events()
    steps = [e["args"] for e in events if e["name"] == "lm/decode_step"]
    assert steps and all(a["ctx_tokens"] > a["window_tokens"] > 0 for a in steps)
    assert steps[-1]["window_tokens"] == WINDOW
    assert any(e["name"] == "lm/window_release" for e in events)
    classes = engine.stats()["kv_classes"]
    assert [k["layers"] for k in classes] == [[0, 5], [1, 2, 3, 4, 6]]
    assert [k["row_lanes"] for k in classes] == [1 * 40, 2 * 40]
    assert all(set(k) >= {"window", "num_blocks", "used_blocks", "free_blocks",
                          "bytes"} for k in classes)
    snap = engine.stats()["metrics"]
    assert snap["window_blocks_released"] > 0
    assert snap["decode_context_tokens"] > snap["decode_window_tokens"] > 0
