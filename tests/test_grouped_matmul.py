"""``ops.grouped_matmul``: the routed experts' weight-streaming kernel,
interpreted on the CPU, against ``lax.ragged_dot`` (the CPU's path and the
kernel's oracle) and a float64 loop over the experts; ``routed_experts`` and a
toy engine with the kernel forced on against themselves on ``ragged_dot``; and
the rule that says which step program takes which.

Tolerances: float32 operands meet in full float32 on both sides and differ by
the order of a row's sums (read here: 0 to 2e-6 of values of size 1); bfloat16
products are rounded to bfloat16 (2**-8 of the value) after a float32 sum, so
two orders of summing differ by a rounding of the result, and SwiGLU's product
of two such by two.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops import grouped_matmul as GM
from bigdl_tpu.ops import _pallas
from bigdl_tpu.parallel import expert as E

TOL = {"float32": 1e-5, "bfloat16": 2.0 ** -6}

#: rows an expert; 48 rows in all, 8 experts
SIZES = {
    "unaligned": [3, 0, 9, 1, 0, 0, 17, 2],     # no multiple of a sublane tile
    "gaps": [0, 5, 0, 0, 11, 0, 4, 0],          # empty experts between hit ones
    "none": [0] * 8,                            # no expert hit
    "one": [0, 0, 0, 48, 0, 0, 0, 0],           # one expert holds every row
    "first-and-last": [7, 0, 0, 0, 0, 0, 0, 30],
    "full": [6] * 8,
}


def _operands(dtype, m, e, k, n, seed=0, poison_from=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    if poison_from is not None:     # rows no expert owns: nothing may leak
        x = x.at[poison_from:].set(jnp.nan)
    w = [jax.random.normal(kk, (e, k, n), jnp.float32) / np.sqrt(k)
         for kk in ks[1:]]
    return x.astype(dtype), [a.astype(dtype) for a in w]


def _float64(x, w, sizes):
    """The loop over the experts: each one's rows against its matrix."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    out, lo = np.zeros((x.shape[0], w.shape[2])), 0
    for e, n in enumerate(sizes):
        out[lo:lo + n] = x[lo:lo + n] @ w[e]
        lo += n
    return out


def _ragged(x, w, sizes):
    return lax.ragged_dot(x, w, jnp.asarray(sizes, jnp.int32),
                          preferred_element_type=jnp.float32).astype(x.dtype)


def _gap(got, want, real):
    got, want = (np.asarray(a, np.float64)[:real] for a in (got, want))
    return float(np.max(np.abs(got - want), initial=0.0)
                 / max(1.0, float(np.max(np.abs(want), initial=0.0))))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [16, 32])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_one_product_equals_ragged_dot_and_the_float64_loop(case, window,
                                                            dtype):
    """Rows past ``sum(sizes)`` are NaN in the input and zeros in the output;
    a window's neighbours' rows are multiplied and dropped."""
    sizes, real = SIZES[case], sum(SIZES[case])
    x, (w, _) = _operands(dtype, 48, 8, 64, 256, poison_from=real)
    s = jnp.asarray(sizes, jnp.int32)
    # two column tiles: a tile's last expert issues the next tile's first copy
    got = GM.grouped_matmul(x, w, s, window=window,
                            tile_bytes=64 * 128 * x.dtype.itemsize)
    assert got.shape == (48, 256) and got.dtype == x.dtype
    assert not np.asarray(got[real:], np.float32).any()       # zeros, no NaN
    assert _gap(got, _ragged(x, w, sizes), real) < TOL[dtype]
    clean = jnp.where(jnp.isnan(x), 0, x)
    assert _gap(got, _float64(clean, w, sizes), real) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_gate_and_up_in_one_call_equal_two_products(case, dtype):
    """``silu(x w_gate) * (x w_up)`` formed in VMEM is what two calls and XLA
    between them give: each product rounded to the rows' dtype first."""
    sizes, real = SIZES[case], sum(SIZES[case])
    x, (wg, wu) = _operands(dtype, 48, 8, 64, 128, seed=1, poison_from=real)
    s = jnp.asarray(sizes, jnp.int32)
    got = GM.grouped_matmul(x, (wg, wu), s, window=16)
    two = (jax.nn.silu(GM.grouped_matmul(x, wg, s, window=16))
           * GM.grouped_matmul(x, wu, s, window=16))
    want = jax.nn.silu(_ragged(x, wg, sizes)) * _ragged(x, wu, sizes)
    assert not np.asarray(got[real:], np.float32).any()
    assert _gap(got, two, real) < 2 * TOL[dtype]
    assert _gap(got, want, real) < 2 * TOL[dtype]


#: the four cells' routed layers, widths cut by 16 (experts whole): (rows,
#: real rows, E, D, F, experts hit) -- GLM's verify round, Solar's, Ling's and
#: Laguna's decode rounds
CELLS = {
    "glm47": (512, 512, 64, 128, 96, 63),
    "solar2": (1024, 128, 40, 256, 80, 19),
    "ling3": (256, 32, 64, 160, 48, 11),
    "laguna_s": (320, 160, 128, 192, 64, 8),
}


def _hit_pattern(rs, e, real, hit):
    sizes = np.zeros(e, np.int64)
    sizes[rs.permutation(e)[:hit]] = 1 + rs.multinomial(
        real - hit, np.ones(hit) / hit)
    return sizes.tolist()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_swiglu_at_the_cells_shapes_and_hit_patterns(cell, monkeypatch):
    """``grouped_swiglu`` through the kernel (gate and up in one call, down
    in its own) at the cells' experts, rows and hit experts, bfloat16."""
    rows, real, e, d, f, hit = CELLS[cell]
    sizes = _hit_pattern(np.random.RandomState(3), e, real, hit)
    x, (wg, wu) = _operands("bfloat16", rows, e, d, f, seed=2,
                            poison_from=real)
    wd = (jax.random.normal(jax.random.PRNGKey(9), (e, f, d), jnp.float32)
          / np.sqrt(f)).astype(jnp.bfloat16)
    p = {"w_gate": wg, "w_up": wu, "w_down": wd}
    s = jnp.asarray(sizes, jnp.int32)
    want, none = E.grouped_swiglu(p, x, s)
    monkeypatch.setattr(E, "expert_matmul_path", lambda *a: "grouped_kernel")
    got, tiles = E.grouped_swiglu(p, x, s)
    assert int(none) == 0 and hit <= int(tiles) <= hit + real // GM.WINDOW + 1
    assert _gap(got, want, real) < 4 * TOL["bfloat16"]


@pytest.mark.parametrize("case", sorted(SIZES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_what_the_kernel_prefetches_and_the_row_tiles_it_visits(case, dtype):
    sizes = SIZES[case]
    ids, hit, offs = GM.hit_experts(jnp.asarray(sizes, jnp.int32))
    want = [e for e, n in enumerate(sizes) if n]
    assert int(hit[0]) == len(want)
    assert np.asarray(ids)[:len(want)].tolist() == want     # in order
    assert np.asarray(offs).tolist() == [0] + np.cumsum(sizes).tolist()
    tile = GM.sublane_tile(dtype)
    assert tile == {"float32": 8, "bfloat16": 16}[dtype]
    for window in (16, 32):
        visits = sum(-(-(lo % tile + n) // window)
                     for lo, n in zip(np.cumsum(sizes) - sizes, sizes) if n)
        assert int(GM.row_tiles(jnp.asarray(sizes, jnp.int32), 48, dtype,
                                window)) == visits
    # a window is no longer than the rows
    assert int(GM.row_tiles(jnp.asarray(sizes, jnp.int32), 48, dtype, 64)) == (
        sum(-(-(lo % tile + n) // 48)
            for lo, n in zip(np.cumsum(sizes) - sizes, sizes) if n))


def test_more_experts_than_rows():
    x, (w, _) = _operands("float32", 8, 24, 32, 128)
    sizes = [0] * 24
    sizes[5], sizes[20] = 3, 5
    s = jnp.asarray(sizes, jnp.int32)
    assert _gap(GM.grouped_matmul(x, w, s), _ragged(x, w, sizes), 8) < 1e-5


@pytest.mark.parametrize("rows", [5, 21, 40])
def test_rows_that_are_no_whole_sublane_tiles_are_padded(rows):
    x, (w, _) = _operands("bfloat16", rows, 4, 32, 128)
    sizes = [rows - 3, 0, 2, 0]
    got = GM.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32))
    assert got.shape == (rows, 128)
    assert _gap(got, _ragged(x, w, sizes), rows - 1) < TOL["bfloat16"]


def test_operands_that_do_not_meet_raise():
    x, (w, _) = _operands("float32", 16, 4, 32, 128)
    with pytest.raises(ValueError, match="do not meet"):
        GM.grouped_matmul(x, w.astype(jnp.bfloat16), jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError, match="do not meet"):
        GM.grouped_matmul(x[:, :16], w, jnp.zeros((4,), jnp.int32))


@pytest.mark.parametrize("k,n,size,want", [
    (2048, 1536, 2, 768),       # GLM's gate and up: two tiles of 3.1 MB
    (1536, 2048, 2, 1024),      # ... and down
    (4096, 1280, 2, 256),       # Solar's: 1,280 = 10 x 128 has no larger fit
    (1280, 4096, 2, 1024),
    (2560, 768, 2, 768),        # Ling's gate and up whole
    (1024, 3072, 2, 1536),      # Laguna's down
    (64, 96, 4, 96),            # a toy width without whole lanes: one tile
])
def test_column_tile_is_the_widest_whole_lane_divisor_that_fits(k, n, size,
                                                                want):
    assert GM.column_tile(k, n, size) == want


# -- who takes it --------------------------------------------------------------
#: (cell, program): the routed layer's (rows, D, F) as the program traces it,
#: and whether its rows lie in VMEM whole (8 MiB of the wider of rows and
#: products)
PROGRAMS = {
    ("glm47", "verify round, 64 slots x 2"): (512, 2048, 1536, True),
    ("glm47", "plain decode, 64 slots"): (256, 2048, 1536, True),
    ("glm47", "prefill 128"): (512, 2048, 1536, True),
    ("glm47", "prefill 512"): (2048, 2048, 1536, True),         # 8 MiB
    ("glm47", "prefill 1024"): (4096, 2048, 1536, False),
    ("glm47", "prefill 2048"): (8192, 2048, 1536, False),
    ("solar2", "decode, 128 slots"): (1024, 4096, 1280, True),  # 8 MiB
    ("solar2", "prefill 256"): (2048, 4096, 1280, False),
    ("solar2", "prefill 1024"): (8192, 4096, 1280, False),
    ("ling3", "decode, 32 slots"): (256, 2560, 768, True),
    ("ling3", "prefill 2048"): (16384, 2560, 768, False),
    ("laguna_s", "decode, 32 slots"): (320, 3072, 1024, True),
    ("laguna_s", "prefill 256"): (2560, 3072, 1024, False),
    ("laguna_s", "prefill 2048"): (20480, 3072, 1024, False),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS), ids=" ".join)
def test_decode_sized_programs_take_the_kernel_on_a_tpu_and_none_on_the_cpu(
        program, monkeypatch):
    """The rule sees the platform and the static shapes, nothing else: the
    same shape takes the same path whatever the model (GLM's 128-token bucket
    is its verify round's, Solar's its decode step's)."""
    *shape, kernel = PROGRAMS[program]
    assert E.expert_matmul_path(*shape, jnp.bfloat16) == "ragged_dot"
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)     # as on a TPU
    assert E.expert_matmul_path(*shape, jnp.bfloat16) == (
        "grouped_kernel" if kernel else "ragged_dot")


# -- routed_experts and an engine, the kernel forced on ---------------------------
def _spec(**kw):
    return E.MoESpec(**{"n_experts": 16, "top_k": 3, "width": 32,
                        "shared_width": 32, "routed_scale": 2.5, **kw})


@pytest.mark.parametrize("case", ["whole", "held half", "idle tokens",
                                  "sigmoid groups"])
def test_routed_experts_with_the_kernel_is_todays_result(case, monkeypatch):
    kw = {"whole": {}, "held half": {"held": (8, 8)}, "idle tokens": {},
          "sigmoid groups": {"score": "sigmoid", "n_group": 4,
                             "topk_group": 2}}[case]
    spec = _spec(**kw)
    p = E.init_routed_params(jax.random.PRNGKey(4), spec, 64)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 64))
    mask = (jnp.arange(21).reshape(3, 7) % 3 != 1) if case == "idle tokens" \
        else None
    want, n = E.routed_mlp(p, x, spec, token_mask=mask)
    monkeypatch.setattr(E, "expert_matmul_path", lambda *a: "grouped_kernel")
    got, m = E.routed_mlp(p, x, spec, token_mask=mask)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert n.shape == m.shape == (spec.n_counts,)
    assert np.asarray(n)[:-1].tolist() == np.asarray(m)[:-1].tolist()
    # the row tiles ride last: none on ragged_dot's path, one or more a hit
    # expert on the kernel's
    assert int(n[-1]) == 0 and int(m[1]) <= int(m[-1]) <= int(m[0])


def test_engine_with_the_kernel_serves_the_same_streams_and_says_so(
        monkeypatch):
    """Toy Laguna through ``LMServingEngine``: the record names the path of
    every step program, the rounds count the row tiles visited and their
    spans carry them."""
    from benchmarks.drivers import serve_laguna as D
    from benchmarks.tests import toy_laguna
    from bigdl_tpu.obs import get_tracer

    prompts = [np.random.RandomState(n).randint(1, 96, size=(n,))
               .astype(np.int32) for n in (5, 11, 17)]

    def serve():
        eng = D.build_engine(toy_laguna.config(), 5)
        try:
            streams = [eng.submit(p, max_new_tokens=7) for p in prompts]
            return [list(s.result(timeout=300)) for s in streams], eng.stats()
        finally:
            eng.close()

    want, plain = serve()
    assert set(plain["expert_matmul"].values()) == {"ragged_dot"}
    assert plain["metrics"]["moe"]["row_tiles"] == 0
    monkeypatch.setattr(E, "expert_matmul_path", lambda *a: "grouped_kernel")
    tracer = get_tracer()
    tracer.enable()
    try:
        got, stats = serve()
        steps = [e for e in tracer.events() if e["name"] == "lm/decode_step"]
    finally:
        tracer.disable()
        tracer.clear()
    assert got == want
    assert stats["expert_matmul"] == {
        "decode": "grouped_kernel", "prefill_8": "grouped_kernel",
        "prefill_16": "grouped_kernel", "prefill_32": "grouped_kernel"}
    moe = stats["metrics"]["moe"]
    assert moe["experts_hit"] <= moe["row_tiles"] <= moe["assignments"]
    assert steps and all(
        s["args"]["moe_experts_hit"] <= s["args"]["moe_row_tiles"]
        <= s["args"]["moe_assignments"] for s in steps)


def test_a_dense_model_has_no_expert_matmul_to_name():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    model = TransformerLM(vocab_size=50, hidden_size=32, n_layers=1, n_head=2,
                          max_len=32).build(seed=0).evaluate()
    eng = LMServingEngine(model, slots=2, cache_len=32, block_len=8,
                          prefill_buckets=(8,))
    try:
        assert eng.stats()["expert_matmul"] is None
    finally:
        eng.close()
