"""``ops.kda_step``: the Pallas kernel of a KDA layer's decode step,
INTERPRETED on the CPU, against ``nn.kda.kda_step`` -- the same float32
arithmetic up to how a sum associates -- over the state arena: active slots
advance, an idle slot's row and every other layer's rows come back bit for
bit, and :func:`kda_step_path` is the whole rule of which form runs.  The
compiled kernel at the cells' shapes is ``tests/test_chip_compile.py``'s."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.kda import kda_step
from bigdl_tpu.ops import kda_step as K
from bigdl_tpu.ops import _pallas

#: (R, S, H, d_k, d_v): a toy, and one slot of each cell's shape
#: (``solar2.backlog`` 64 heads, ``ling3.longdecode`` 32, of 128 x 128)
SHAPES = {"toy": (3, 5, 16, 16, 128), "solar2": (2, 1, 64, 128, 128),
          "ling3": (2, 1, 32, 128, 128)}
#: which of the slots decode (cut to a shape's slots)
ACTIVE = {"all": [1, 1, 1, 1, 1], "some": [0, 1, 0, 1, 1],
          "one": [0, 0, 0, 1, 0], "none": [0, 0, 0, 0, 0]}


def _inputs(shape, seed=0, beta_top=1.0, forget=None):
    r, s, h, dk, dv = shape
    rng = np.random.default_rng(seed)
    f32 = lambda *n: jnp.asarray(rng.standard_normal(n), jnp.float32)  # noqa: E731
    state = f32(r, s, h, dk, dv)
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in (f32(s, h, dk), f32(s, h, dk)))
    g = -jnp.exp(f32(s, h, dk) - 2.0)
    if forget is not None:      # a channel that forgets by e^forget in a step
        g = g.at[:, :, ::3].set(forget)
    beta = jnp.asarray(rng.uniform(0.0, beta_top, (s, h)), jnp.float32)
    return state, (q, k, f32(s, h, dv), g, beta)


def _oracle(state, layer, active, q, k, v, g, beta):
    """Today's XLA path: the layer's rows through ``kda_step``, an idle
    slot's kept, written back."""
    row = state[layer]
    o, new = kda_step(q, k, v, g, beta, row)
    new = jnp.where(active[:, None, None, None], new, row)
    return o, state.at[layer].set(new)


def _check(shape, active, layer, x_kw=None):
    state, x = _inputs(shape, **(x_kw or {}))
    active = jnp.asarray(active[:shape[1]], bool)
    o, new = K.kda_step_rows(state, jnp.int32(layer), active, *x, interpret=True)
    ref_o, ref = _oracle(state, layer, active, *x)
    assert new.dtype == jnp.float32 and o.dtype == jnp.float32
    idle = ~np.asarray(active)
    # an idle slot's row and the other layers' rows: BIT for bit
    assert np.array_equal(np.asarray(new[layer])[idle], np.asarray(state[layer])[idle])
    others = [r for r in range(shape[0]) if r != layer]
    assert np.array_equal(np.asarray(new)[others], np.asarray(state)[others])
    # the active ones: the same products, sums in another order
    scale = float(jnp.max(jnp.abs(ref[layer]))) or 1.0
    assert float(jnp.max(jnp.abs(new - ref))) <= 1e-6 * scale
    live = np.asarray(active)
    o_scale = float(jnp.max(jnp.abs(ref_o))) or 1.0
    assert float(jnp.max(jnp.abs(np.asarray(o - ref_o)[live]), initial=0.0)) <= 2e-6 * o_scale
    assert not np.asarray(o)[idle].any()            # zeros, never garbage
    return state, new


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("active", ["all", "none"])
def test_kernel_equals_kda_step_at_the_toy_and_one_slot_of_each_cell(shape, active):
    state, new = _check(SHAPES[shape], ACTIVE[active], layer=0)
    if active == "none":        # the arena comes back untouched
        assert np.array_equal(np.asarray(new), np.asarray(state))


@pytest.mark.parametrize("active", ["some", "one"])
@pytest.mark.parametrize("layer", [0, 2])
def test_idle_rows_and_other_layers_come_back_bit_identical(active, layer):
    _check(SHAPES["toy"], ACTIVE[active], layer=layer)


def test_beta_above_one_and_a_channel_that_forgets_by_e_minus_88():
    """``beta`` in (1, 2) (the transition's negative eigenvalue) and ``g`` =
    -88 on every third channel: ``exp(g)`` is the smallest normal float32 or
    under it, and nothing of the step overflows or leaves the oracle."""
    state, new = _check(SHAPES["toy"], ACTIVE["some"], layer=1,
                        x_kw={"beta_top": 2.0, "forget": -88.0, "seed": 5})
    assert bool(jnp.all(jnp.isfinite(new)))


def test_a_traced_layer_under_jit_with_the_arena_donated():
    """As the decode step calls it: inside a jitted program, the layer's index
    traced, several steps in a row."""
    shape = SHAPES["toy"]
    state, x = _inputs(shape, seed=2)
    active = jnp.asarray(ACTIVE["some"], bool)

    def steps(fn):
        def run(state, layers):
            def body(state, layer):
                o, state = fn(state, layer, active, *x)
                return state, o
            return jax.lax.scan(body, state, layers)
        return jax.jit(run)

    layers = jnp.asarray([2, 0, 2, 1], jnp.int32)
    new, o = steps(lambda *a: K.kda_step_rows(*a, interpret=True))(state, layers)
    ref, ref_o = steps(_oracle)(state, layers)
    assert float(jnp.max(jnp.abs(new - ref))) <= 1e-5 * float(jnp.max(jnp.abs(ref)))
    live = np.asarray(active)
    assert np.allclose(np.asarray(o)[:, live], np.asarray(ref_o)[:, live],
                       rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(ref_o))))
    assert np.array_equal(np.asarray(new)[:, ~live], np.asarray(state)[:, ~live])


def test_a_state_that_is_not_float32_or_heads_the_block_does_not_divide_raise():
    state, x = _inputs(SHAPES["toy"])
    active = jnp.ones((5,), bool)
    with pytest.raises(ValueError, match="float32"):
        K.kda_step_rows(state.astype(jnp.bfloat16), 0, active, *x)
    state, x = _inputs((1, 5, 12, 16, 128))
    with pytest.raises(ValueError, match="12 heads do not divide"):
        K.kda_step_rows(state, 0, active, *x)


@pytest.mark.parametrize("case,shape,expected", [
    ("the cpu", (64, 128, 128), "xla"),
    ("a tpu, solar2's state", (64, 128, 128), "kernel"),
    ("a tpu, ling3's state", (32, 128, 128), "kernel"),
    ("a tpu, d_v not whole lane tiles", (64, 128, 64), "xla"),
    ("a tpu, d_k not whole sublane tiles", (64, 12, 128), "xla"),
    ("a tpu, heads the block does not divide", (12, 128, 128), "xla"),
])
def test_kda_step_path_is_the_platform_and_the_shape(monkeypatch, case, shape,
                                                     expected):
    """No argument, no environment variable, no model's name: the CPU takes
    ``kda_step``; a TPU (``use_interpret`` false, steered here as
    ``tests/test_chip_compile.py`` steers it) the kernel where a head's state
    is whole tiles and the heads divide into blocks."""
    if case != "the cpu":
        monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    assert K.kda_step_path(*shape) == expected


def test_active_slots_lists_the_active_first_and_counts_them():
    ids, count = K.active_slots(jnp.asarray([0, 1, 0, 1, 1], bool))
    assert ids.tolist() == [1, 3, 4, 0, 2] and count.tolist() == [3]
    ids, count = K.active_slots(jnp.zeros((4,), bool))
    assert ids.tolist() == [0, 1, 2, 3] and count.tolist() == [0]
