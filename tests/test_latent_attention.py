"""The absorbed latent decode kernel (``bigdl_tpu/ops/latent_attention.py``)
against its oracle, the XLA walk over the live list
(``generate._paged_attention(v=None)``): the same arena, the same blocks, the
same new rows, at toy geometry in the interpreter.  What the kernel changes is
the order of the float32 sums, so the two agree to 1e-5 of the output's size;
and an engine that serves through the kernel serves the logits the walk serves.
The kernel compiled for the chip is ``tests/test_chip_compile.py``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers import serve_ling3 as D
from benchmarks.harness import reference_ling3 as R
from benchmarks.tests import toy_ling3
from benchmarks.tests.served import Served
from bigdl_tpu.models.transformer import generate as G
from bigdl_tpu.ops import latent_attention as la
from bigdl_tpu.serving.kvcache.blocks import SCRATCH_BLOCK, live_list

REL = 1e-5
TOL = 2e-4          # tests/test_ling3.py's, served logits against the reference
SEED = 5
FETCH = 4           # blocks a grid step fetches in these cases


def _case(lengths, *, block_len=16, table_width=12, heads=4, row=40,
          layers=2, layer=1, dtype=jnp.bfloat16, seed=0):
    """A latent arena of random rows, a chain of scattered blocks a slot as long
    as ``lengths`` says (0: an idle slot), absorbed queries and the round's new
    rows -> what the walk and the kernel are handed."""
    slots, B, M = len(lengths), block_len, table_width
    rng = np.random.default_rng(seed)
    n = slots * M + 1
    arena = jnp.asarray(rng.standard_normal((layers, n, B, 128)), dtype)
    arena = arena.at[..., row:].set(0)                      # the lane padding
    q = jnp.asarray(2 * rng.standard_normal((slots, heads, 1, row)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((slots, 1, 1, row)), dtype)
    order = rng.permutation(np.arange(1, n))
    chains, tables = [], np.full((slots, M), SCRATCH_BLOCK, np.int32)
    for s, length in enumerate(lengths):
        held = -(-length // B)
        if held:
            chains.append((s, order[s * M:s * M + held]))
            tables[s, :held] = chains[-1][1]
    live = jnp.asarray(live_list(chains, slots * M, slots))
    pos = jnp.asarray([max(length - 1, 0) for length in lengths], jnp.int32)
    return dict(arena=arena, q=q, new=new, live=live, pos=pos, B=B,
                tables=jnp.asarray(tables), layer=layer,
                lengths=jnp.asarray(lengths, jnp.int32))


def _walk(c, score_dim=24):
    """The oracle: the new rows written, then the list walked -> (o, arena)."""
    ids, owner, where = c["live"]
    slots, B, pos = c["pos"].shape[0], c["B"], c["pos"]
    held = (owner[None, :] == jnp.arange(slots)[:, None]) & (ids != 0)[None, :]
    blk = jnp.max(jnp.where(held & (where[None, :] == (pos // B)[:, None]),
                            ids[None, :], 0), axis=1)[:, None]
    k_pos = where[:, None] * B + jnp.arange(B)[None, :]
    mask = ((k_pos <= pos[jnp.minimum(owner, slots - 1)][:, None])
            & (owner < slots)[:, None])[:, None, :]
    o, (arena,) = G._paged_attention(c["q"], c["new"], None, (c["arena"],),
                                     c["layer"], blk, (pos % B)[:, None],
                                     c["live"], mask, score_dim=score_dim)
    return o, arena


def _close(got, want):
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(got - want))) < REL * float(jnp.max(jnp.abs(want)))


CHAINS = {
    "one-block": [16, 3, 1],
    "exactly-a-step": [64, 64],                 # FETCH blocks, no more
    "a-step-and-a-block": [65, 80, 64],
    "ending-mid-block": [70, 41, 9, 119],
    "an-idle-slot-between": [50, 0, 130],
    "scratch-padding-behind-a-short-chain": [5, 192],
    "every-entry-of-the-table": [192, 192],
}


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_the_kernel_reads_what_the_walk_reads(name):
    c = _case(CHAINS[name])
    want, arena = _walk(c)
    got = la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"],
                                     score_dim=24, layer=c["layer"],
                                     blocks_per_step=FETCH)
    _close(got, want)
    idle = np.asarray(c["lengths"]) == 0
    assert not np.asarray(got)[idle].any()                  # zeros, as the walk's
    assert not np.asarray(want)[idle].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block_len", [16, 32])
def test_block_lengths_and_row_dtypes(block_len, dtype):
    """bfloat16 rows meet the operands in three pieces, float32 rows at the
    highest precision, as the walk's."""
    c = _case([block_len * 5 + 3, 1, 0, block_len * 2], block_len=block_len,
              table_width=6, dtype=jnp.dtype(dtype))
    want, arena = _walk(c)
    got = la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"],
                                     score_dim=24, layer=1, blocks_per_step=2)
    _close(got, want)


@pytest.mark.parametrize("layers,layer", [(3, 0), (3, 2), (1, 0)])
def test_the_layer_is_an_operand_of_the_whole_arena(layers, layer):
    """A traced layer index of an arena with several layers (the decode step's:
    the arena rides the layer scan whole), and one layer's own arena."""
    c = _case([37, 100], layers=layers, layer=layer, seed=3)
    want, arena = _walk(c)

    def attend(layer, arena):
        return la.latent_decode_attention(
            c["q"], arena, c["tables"], c["lengths"], score_dim=24, layer=layer,
            blocks_per_step=FETCH)

    _close(jax.jit(attend)(jnp.int32(layer), arena), want)
    if layers == 1:
        _close(la.latent_decode_attention(
            c["q"][:, :, 0], arena[0], c["tables"], c["lengths"], score_dim=24,
            blocks_per_step=FETCH), want[:, :, 0])


@pytest.mark.parametrize("fetch,value_lanes", [(1, None), (5, 24), (12, 40),
                                               (64, 8)])
def test_steps_of_any_size_and_the_value_lanes(fetch, value_lanes):
    """A step of one block, one that does not divide the table, the whole table
    and more; only the leading ``value_lanes`` of a row are values."""
    c = _case([7, 150, 33], seed=fetch)
    want, arena = _walk(c)
    got = la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"],
                                     score_dim=24, layer=1,
                                     value_lanes=value_lanes,
                                     blocks_per_step=fetch)
    _close(got, want[..., :value_lanes])


def test_the_kernel_only_reads_the_arena():
    c = _case([20, 60])
    _, arena = _walk(c)
    before = np.asarray(arena, np.float32)
    la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"],
                               score_dim=24, layer=1).block_until_ready()
    assert (np.asarray(arena, np.float32) == before).all()


@pytest.mark.parametrize("block_len,lanes,dtype,says", [
    (8, 640, "bfloat16", "multiple of 16"),
    (4, 128, "float32", "multiple of 8"),
    (16, 576, "bfloat16", "whole 128-lane tiles"),
])
def test_a_geometry_the_compiled_kernel_cannot_take_raises(block_len, lanes,
                                                           dtype, says):
    with pytest.raises(ValueError, match=says):
        la.check_latent_kernel_shapes(block_len, lanes, jnp.dtype(dtype))
    arena = jnp.zeros((1, 3, block_len, lanes), jnp.dtype(dtype))
    with pytest.raises(ValueError, match=says):
        la.latent_decode_attention(
            jnp.zeros((1, 2, 64)), arena, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), score_dim=24, layer=0, interpret=False)
    la.check_latent_kernel_shapes(16, 640, jnp.bfloat16)    # the cell's
    la.check_latent_kernel_shapes(8, 128, jnp.float32)


# -- an engine that serves through the kernel ---------------------------------
def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 96, size=(n,)).astype(np.int32)


def _engine(decode_attn):
    c = toy_ling3.config()
    c["engine"] = dict(c["engine"], decode_attn=decode_attn)
    return D.build_engine(c, SEED)


@pytest.fixture(scope="module")
def engines():
    both = {impl: _engine(impl) for impl in ("paged_kernel", "gather")}
    yield both
    for eng in both.values():
        eng.close()


@pytest.fixture(scope="module")
def reference_weights():
    return R.make_weights(SEED, toy_ling3.config(), "float32")


JOBS = {
    "one-stream-beside-idle-slots": [(11, 15)],
    "a-prompt-in-chunks": [(45, 8)],
    "six-requests-over-four-slots": [(5, 4), (30, 9), (12, 13), (8, 6), (17, 7),
                                     (3, 11)],
}


@pytest.mark.parametrize("name", sorted(JOBS))
def test_an_engine_serves_through_the_kernel_what_the_walk_serves(
        monkeypatch, engines, reference_weights, name):
    """``decode_attn="paged_kernel"`` on a latent pool: the same requests,
    teacher-forced, through both engines; the logits agree with each other and
    with the plain reference to ``tests/test_ling3.py``'s tolerance."""
    c = toy_ling3.config()
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
    for (prompt, forced), got, walked in zip(jobs, rows["paged_kernel"],
                                             rows["gather"]):
        ids = np.concatenate([prompt, forced])
        t = len(prompt)
        want = np.asarray(R.forward(reference_weights, c, ids))[t - 1:t - 1 + len(forced)]
        assert got.shape == want.shape
        assert np.max(np.abs(got - walked)) < TOL
        assert np.max(np.abs(got - want)) < TOL


# -- W query positions a slot: a verify step's candidate rows -------------------------
def _rows_case(lengths, w, *, first=0, seed=0, **kw):
    """:func:`_case` with ``w`` new rows a slot: row i is the slot's position
    ``length - 1 + i`` (an idle slot has none); the chains reach the last
    row's block.  -> the case, and the walk's reading of it: the rows written,
    then row i attending positions ``first <= p <= length - 1 + i``."""
    c = _case([n + w - 1 if n else 0 for n in lengths], seed=seed, **kw)
    slots, B = len(lengths), c["B"]
    rng = np.random.default_rng(seed + 100)
    heads, row = c["q"].shape[1], c["q"].shape[3]
    q = jnp.asarray(2 * rng.standard_normal((slots, heads, w, row)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((slots, 1, w, row)), c["arena"].dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    pos = jnp.maximum(lengths - 1, 0)[:, None] + jnp.arange(w)[None, :]
    blk = jnp.where((lengths > 0)[:, None],
                    c["tables"][jnp.arange(slots)[:, None], pos // B], 0)
    ids, owner, where = c["live"]
    k_pos = where[:, None] * B + jnp.arange(B)[None, :]
    own = jnp.minimum(owner, slots - 1)
    mask = ((k_pos[:, None, :] <= pos[own][:, :, None])
            & (k_pos >= first)[:, None, :] & (owner < slots)[:, None, None])
    o, (arena,) = G._paged_attention(q, new, None, (c["arena"],), c["layer"],
                                     blk, pos % B, c["live"], mask, score_dim=24)
    return dict(c, q=q, lengths=lengths), o, arena


@pytest.mark.parametrize("first", [0, 1])
@pytest.mark.parametrize("w,lengths", [
    (2, [16, 3, 1]),                    # the second row opens a block
    (2, [64, 63, 0, 130]),              # ... a grid step; an idle slot
    (2, [5, 191]),                      # the table's last entry
    (3, [70, 41, 9, 119]),
])
def test_the_kernel_reads_w_rows_a_slot_as_the_walk(w, lengths, first):
    """W static: row i of a slot sees ``lengths + i`` positions (its mask a
    row, the softmax's parts a (head, row)), from ``first`` on (a prediction
    module's rows start at 1)."""
    c, want, arena = _rows_case(lengths, w, first=first, seed=w)
    got = la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"],
                                     score_dim=24, layer=c["layer"],
                                     blocks_per_step=FETCH, first=first)
    active = np.asarray(c["lengths"]) > 0
    assert got.shape == want.shape == (len(lengths), 4, w, 40)
    _close(got[active], want[active])
    assert not np.asarray(got)[~active].any()


def test_one_row_a_slot_is_the_kernel_as_it_was():
    """W = 1 through the (S, H, 1, D) form lowers to the call the (S, H, D)
    form makes: the same kernel, the same parameters, the same bits."""
    c = _case([37, 100, 0, 64])
    _, arena = _walk(c)
    kw = dict(score_dim=24, layer=1, blocks_per_step=FETCH)
    four = la.latent_decode_attention(c["q"], arena, c["tables"], c["lengths"], **kw)
    three = la.latent_decode_attention(c["q"][:, :, 0], arena, c["tables"],
                                       c["lengths"], **kw)
    assert (np.asarray(four[:, :, 0]) == np.asarray(three)).all()
    # ... and the kernel's own program is the same, equation for equation
    def kernel(q):
        eqns = jax.make_jaxpr(lambda q, a: la.latent_decode_attention(
            q, a, c["tables"], c["lengths"], **kw))(q, arena).jaxpr.eqns
        call, = [e for e in eqns if e.primitive.name == "pallas_call"]
        return str(call.params["jaxpr"])

    assert kernel(c["q"]) == kernel(c["q"][:, :, 0])


def test_a_self_drafting_engine_serves_through_the_kernel_what_the_walk_serves():
    """GLM-4.7-Flash's toy twin, its prediction module as the drafter: the
    round's verify rows (W = 2) and the module's pairs (from position 1 on)
    through the kernel, interpreted, against the walk: the same tokens, the
    same drafts."""
    from benchmarks.drivers import serve_glm47
    from benchmarks.tests import toy_glm47
    c = toy_glm47.config()
    jobs = [(_ids(n, 40 + i) + 1, m) for i, (n, m) in enumerate(
        [(6, 14), (21, 9), (37, 11)])]
    got = {}
    for impl in ("paged_kernel", "gather"):
        eng = serve_glm47.build_engine(c, SEED, decode_attn=impl)
        try:
            assert eng.stats()["decode_attn"] == impl
            streams = [eng.submit(p, max_new_tokens=m) for p, m in jobs]
            got[impl] = ([s.result(timeout=600) for s in streams],
                         [s.drafts for s in streams])
        finally:
            eng.close()
    for a, b in zip(*[got[i][0] for i in got]):
        assert (a == b).all()
    assert got["paged_kernel"][1] == got["gather"][1]
    assert sum(len(d) for d in got["gather"][1]) > 10
