"""Laguna-S-2.1's block through ``TransformerLM`` and ``LMServingEngine`` at a
toy size, against the plain reference the benchmark keeps
(``benchmarks/harness/reference_laguna.py``): hidden 64, 2 K/V heads of 16,
6 query heads on sliding layers (window 8) and 4 on full ones (half of each
head rotated, YaRN), 16 routed experts top-3 of which 8 are held, a shared
expert, one dense layer and two whole periods (sliding, sliding, sliding,
full) -- nine layers in two groups of the layer plan.

LOGITS are compared, not tokens.  Tolerance: both sides compute in float32
on the CPU (the program at XLA's default, which is full float32 there; the
reference at ``highest``); they differ by the order of their sums, 1e-6 to
4e-6 on logits of size 5 (read on this toy, every test below).  2e-4 leaves
fifty times that and is eighteen times under what rounding the expert
matmuls' activations to bfloat16 moves (3.7e-3: the last test of the file).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_laguna as D
from benchmarks.harness import reference_laguna as R
from benchmarks.tests import toy_laguna
from bigdl_tpu.parallel import expert as E

TOL = 2e-4
SEED = 5


toy = toy_laguna.config


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


def test_layer_plan_is_a_dense_layer_and_two_whole_periods():
    model = D.build_model(toy())
    (lead, dense), (repeat, period) = model.plan
    assert (lead, repeat, len(period)) == (1, 2, 4) and model.moe_layers == 8
    assert [s.n_head for s in dense + period] == [4, 6, 6, 6, 4]
    assert [s.window for s in dense + period] == [None, 8, 8, 8, None]
    assert [s.mlp for s in dense + period] == ["dense"] + ["moe"] * 4
    # the published period, full layer first, has the same four layers
    assert model.head_dim == 16 and model.n_kv_head == 2


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_full_forward_matches_the_reference(reference_weights, impl):
    """The training-side forward (one sequence longer than the window); with
    ``flash`` the window term and the grouped heads run inside the Pallas
    kernel's tiles (interpreted here, tiles of 8)."""
    c = toy(assumed={"serve_dtype": "float32", "attention_impl": impl,
                     "flash_block": 8 if impl == "flash" else None})
    model, ids = _model(c), _ids(29)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) < TOL


def _served_logits(monkeypatch, engine, prompt, forced):
    """Serve ``prompt`` teacher-forced on ``forced`` (0-based): every logits
    row the engine picks a token from, in order.  The first token's row
    reaches the host's ``_pick``; a decode round picks on the device, so the
    engine's decode executable is stood in for by the same step handing out
    its logits, and the forced token as every slot's id."""
    from bigdl_tpu.models.transformer import generate as G
    from bigdl_tpu.serving import lm_engine
    from bigdl_tpu.serving.kvcache.blocks import SCRATCH_BLOCK
    rows, queue = [], list(forced)

    def pick(logits_row, temperature, key, clamp):
        rows.append(np.array(logits_row))
        return int(queue.pop(0))

    step = jax.jit(
        lambda p, token, pos, live, *kv: G._decode_step_paged(
            engine.model, p, token, pos, live, *kv,
            table_width=engine.table_width, attn_impl=engine.decode_attn),
        donate_argnums=(4, 5))

    def decode(params, operands, prev_ids, *kv):
        token, pos, _, _, live = lm_engine.split_decode_operands(
            jnp.asarray(operands), engine.slots)
        token = jnp.where(token < 0, prev_ids, token)   # lm_engine.TAKE_PREV
        logits, *rest = step(params, token, pos, live, *kv)
        block, owner, _ = np.asarray(live)
        slot, = set(owner[block != SCRATCH_BLOCK].tolist())     # one request
        rows.append(np.array(logits[slot]))
        return (jnp.full((engine.slots,), queue.pop(0), jnp.int32), *rest)

    monkeypatch.setattr(lm_engine.LMServingEngine, "_pick", staticmethod(pick))
    monkeypatch.setattr(engine, "_decode_exec", decode)
    engine.submit(prompt + 1, max_new_tokens=len(forced)).result(timeout=300)
    return np.stack(rows)


@pytest.fixture(scope="module")
def engine():
    eng = D.build_engine(toy(), SEED)
    yield eng
    eng.close()


def test_prefill_then_paged_decode_matches_the_reference(
        monkeypatch, engine, reference_weights):
    """Through ``LMServingEngine.submit``: a prompt of 19 (bucket 32, past the
    window of 8), then 14 decode rounds with three idle slots beside it."""
    c, prompt, forced = toy(), _ids(19, 1), _ids(15, 2)
    assert engine.decode_attn == "gather"
    assert engine.pool.shape[-1] == 128         # a row: 2 K/V heads x 16, padded
    got = _served_logits(monkeypatch, engine, prompt, forced)
    ids = np.concatenate([prompt, forced])
    want = np.asarray(R.forward(reference_weights, c, ids))[18:18 + 15]
    assert np.max(np.abs(got - want)) < TOL
    moe = engine.stats()["metrics"]["moe"]
    # 14 rounds x 8 routed layers; one live token a round, 3 picks of 16 a
    # layer, those on held experts land; every hit expert has an assignment
    assert moe["expert_layer_rounds"] == 14 * 8
    assert 0 < moe["experts_hit"] <= moe["assignments"] <= 14 * 8 * 3
    assert 0 < moe["prefill_assignments"] <= 32 * 8 * 3


def test_suffix_prefill_over_a_cached_prefix_crosses_the_window(
        monkeypatch, engine, reference_weights):
    """A second prompt shares 16 tokens (4 blocks) with a served one: its 9
    other tokens prefill against the cached chain, and the first of them sees
    7 cached positions through the window."""
    c, first = toy(), _ids(24, 3)
    _served_logits(monkeypatch, engine, first, _ids(2, 4))
    second = np.concatenate([first[:16], _ids(9, 5)])
    before = engine.stats()["prefix_prefill_cache"]["misses"]
    forced = _ids(6, 6)
    got = _served_logits(monkeypatch, engine, second, forced)
    assert engine.stats()["prefix_prefill_cache"]["misses"] == before + 1
    ids = np.concatenate([second, forced])
    want = np.asarray(R.forward(reference_weights, c, ids))[24:24 + 6]
    assert np.max(np.abs(got - want)) < TOL


def test_concurrent_streams_are_the_single_streams(engine):
    """Four requests sharing the decode rounds (slots fill and free) emit what
    each emits alone: a slot's routing never sees its neighbours."""
    prompts = [_ids(n, 10 + n) + 1 for n in (5, 11, 17, 23)]
    alone = [list(engine.submit(p, max_new_tokens=9).result(timeout=300))
             for p in prompts]
    streams = [engine.submit(p, max_new_tokens=9) for p in prompts]
    assert [list(s.result(timeout=300)) for s in streams] == alone


# -- the expert layer -----------------------------------------------------------
def _uncut():
    """The toy's first sparse layer with all 16 experts here."""
    c = toy(num_experts=16, expert_share=[0, 1])
    w = R.make_layer(SEED, c, 1, "float32")
    return c, w, D.program_layer(w)["moe"]


def _spec(c, held, **kw):
    return E.MoESpec(n_experts=16, top_k=3, width=32, shared_width=32,
                     routed_scale=2.5, held=held, **kw)


def test_the_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    c, w, p = _uncut()
    m = jax.random.normal(jax.random.PRNGKey(1), (37, 64))
    with jax.default_matmul_precision("highest"):
        weights = R.routing(c, m, w["router"])
        routed = sum(weights[:, e:e + 1] * R._swiglu(
            m, w["e_gate"][e], w["e_up"][e], w["e_down"][e]) for e in range(16))
        shared = R._swiglu(m, w["s_gate"], w["s_up"], w["s_down"])
    whole, counts = E.routed_mlp(p, m, _spec(c, None))
    assert float(jnp.max(jnp.abs(whole - (routed + shared)))) < 1e-5
    assert int(counts[0]) == 37 * 3
    parts, landed = [], 0
    for first in (0, 8):
        half = dict(p, **{k: p[k][first:first + 8]
                          for k in ("w_gate", "w_up", "w_down")})
        y, n = E.routed_experts(half, m, _spec(c, (first, 8)))
        parts.append(y)
        landed += int(n[0])
    assert landed == 37 * 3                     # every pick lands on one share
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.01     # neither share is empty


def test_under_an_expert_mesh_axis_the_same_body_and_a_psum():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    c, _, p = _uncut()
    p = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    m = jax.random.normal(jax.random.PRNGKey(2), (21, 64))
    spec = _spec(c, None)
    whole, _ = E.routed_experts(p, m, spec)
    mesh = Mesh(np.array(jax.devices()[:2]), ("expert",))
    pspec = {"router": P(), "w_gate": P("expert"), "w_up": P("expert"),
             "w_down": P("expert")}
    fn = shard_map(lambda p, x: E.routed_experts(p, x, spec, axis="expert"),
                   mesh=mesh, in_specs=(pspec, P()), out_specs=(P(), P("expert")))
    y, counts = fn(p, m)
    assert float(jnp.max(jnp.abs(y - whole))) < 1e-5
    assert int(counts.reshape(2, spec.n_counts)[:, 0].sum()) == 21 * 3


@pytest.mark.parametrize("sizes", [
    [0, 40, 0, 0], [13, 0, 20, 7], [40, 0, 0, 0], [10, 10, 10, 10],
    [0, 0, 0, 25]])
def test_grouped_matmul_against_the_all_experts_einsum(sizes):
    """Skewed routing: experts with no row, one with every row, rows past the
    last group (assignments that landed elsewhere)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    p = {"w_gate": 0.3 * jax.random.normal(ks[0], (4, 16, 8)),
         "w_up": 0.3 * jax.random.normal(ks[1], (4, 16, 8)),
         "w_down": 0.3 * jax.random.normal(ks[2], (4, 8, 16))}
    xs = jax.random.normal(ks[3], (40, 16))
    got, tiles = E.grouped_swiglu(p, xs, jnp.asarray(sizes, jnp.int32))
    assert int(tiles) == 0          # lax.ragged_dot's path visits no row tile
    every = jnp.einsum(
        "etf,efd->etd",
        jax.nn.silu(jnp.einsum("td,edf->etf", xs, p["w_gate"]))
        * jnp.einsum("td,edf->etf", xs, p["w_up"]), p["w_down"])
    owner = np.repeat(np.arange(4), sizes)
    n = len(owner)
    want = every[owner, np.arange(n)]
    assert float(jnp.max(jnp.abs(got[:n] - want))) < 1e-4


def test_idle_tokens_are_routed_to_no_expert():
    c, _, p = _uncut()
    m = jax.random.normal(jax.random.PRNGKey(4), (6, 64))
    mask = jnp.asarray([True, False, True, False, False, False])
    y, counts = E.routed_experts(p, m, _spec(c, None), token_mask=mask)
    alone, _ = E.routed_experts(p, m[jnp.asarray([0, 2])], _spec(c, None))
    assert int(counts[0]) == 6 and float(jnp.max(jnp.abs(y[1]))) == 0.0
    assert float(jnp.max(jnp.abs(y[jnp.asarray([0, 2])] - alone))) < 1e-6


# -- GPT-2 is a plan of one group on the same programs ---------------------------
#: what the parent commit (b15d333) served and generated for these models and
#: prompts (recorded there on the CPU): 1-based ids, prompt included where served
GPT2_BEFORE = json.loads(
    '{"learned": {"offline": [[36, 36, 36, 36, 36, 36, 36, 36, 36, 36], [11, 32, 32, 32, 32, 32, 32, 32, 32, 32], [46, 46, 46, 17, 14, 2, 2, 2, 35, 35]], "served": [[43, 25, 58, 4, 57, 36, 36, 36, 36, 36, 36, 36, 36, 36, 36], [9, 1, 22, 20, 11, 44, 58, 42, 11, 11, 32, 32, 32, 32, 32, 32, 32, 32, 32], [22, 56, 39, 33, 21, 45, 30, 40, 15, 57, 27, 18, 27, 23, 46, 46, 46, 17, 14, 2, 2, 2, 35, 35]]}, "rope": {"offline": [[11, 11, 11, 11, 11, 11, 11, 11, 11, 11], [11, 11, 11, 11, 11, 11, 11, 11, 11, 11], [23, 23, 23, 23, 23, 23, 23, 23, 23, 23]], "served": [[43, 25, 58, 4, 57, 11, 11, 11, 11, 11, 11, 11, 11, 11, 11], [9, 1, 22, 20, 11, 44, 58, 42, 11, 11, 11, 11, 11, 11, 11, 11, 11, 11, 11], [22, 56, 39, 33, 21, 45, 30, 40, 15, 57, 27, 18, 27, 23, 23, 23, 23, 23, 23, 23, 23, 23, 23, 23]]}}')


@pytest.mark.parametrize("pos", ["learned", "rope"])
def test_gpt2_greedy_streams_are_token_exact_with_the_parent_commit(pos):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.models.transformer.generate import generate
    from bigdl_tpu.serving import LMServingEngine
    kw = {"pos_encoding": "rope"} if pos == "rope" else {}
    m = TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=3,
                      max_len=48, **kw).build(seed=11).evaluate()
    assert m.plan == ((3, m.plan[0][1]),) and "blocks" in m.params
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 62, size=n) for n in (5, 9, 14)]
    offline = [np.asarray(generate(m, m.params, p[None], 10))[0, len(p):].tolist()
               for p in prompts]
    with LMServingEngine(m, slots=2, block_len=4, cache_len=48,
                         prefill_buckets=(8, 16), max_new_tokens=10) as eng:
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
        served = [list(map(int, s.result(timeout=300))) for s in streams]
    assert offline == GPT2_BEFORE[pos]["offline"]
    assert served == GPT2_BEFORE[pos]["served"]


# -- the tolerance is tight enough ----------------------------------------------
def test_bfloat16_expert_matmuls_under_a_float32_configuration_fail_the_tolerance(
        reference_weights):
    c = toy()
    model, ids = _model(c), _ids(29)
    want = jax.nn.log_softmax(R.forward(reference_weights, c, ids))
    with toy_laguna.experts_rounded("bfloat16"):
        got = model.f(model.params, jnp.asarray(ids[None] + 1))[0]
    assert float(jnp.max(jnp.abs(want - got))) > 10 * TOL
