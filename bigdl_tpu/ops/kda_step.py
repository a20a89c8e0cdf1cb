"""A KDA layer's decode step over the state arena as a Pallas TPU kernel.

``nn.kda.kda_step`` is one position of the gated delta rule::

    decayed = S * exp(g)[:, None]             seen = sum(decayed * k[:, None], d_k)
    S'      = decayed + k[:, None] * (beta * (v - seen))[None, :]
    o       = sum(S' * q[:, None], d_k) / sqrt(d_k)

``seen`` is a reduction of the whole decayed state and ``o`` one of the NEW
state, so as XLA fusions the state crosses HBM between them: two reduction
passes, then the ``select`` (an idle slot keeps its row) and the
``dynamic-update-slice`` that writes the layer's rows into the arena -- five to
six passes over a state that has to be read once and written once (PERF.md,
PR 43).  A head's state is ``d_k x d_v`` float32, 64 KiB at 128 x 128: it
fits VMEM many times over and everything the step does to it is elementwise
or a sum over sublanes.  So this kernel takes the WHOLE arena ``(R, S, H,
d_k, d_v)`` (``serving.kvcache.state``), aliased input to output, with the
layer's index and the ACTIVE slots as scalar-prefetch operands (as
``ops.grouped_matmul`` takes the hit experts): a grid step owns one active
slot's block of :data:`HEADS_BLOCK` heads, does the arithmetic above with the tile
in VMEM and writes ``S'`` to the block it read.  A slot that does not decode
is never visited: its row moves no bytes and stays bit for bit what it was
(the steps past the active count map to the last block visited and skip
their body), and so do the other layers' rows.

The arithmetic is ``kda_step``'s as written: float32 state, float32
elementwise products, float32 sums (which may associate differently); no
matrix-unit product, whose default precision would round the state to
bfloat16.  ``exp(g)`` and the ``1 / sqrt(d_k)`` are taken in XLA on the
small operands, as ``kda_step`` takes them.  ``g``, ``k`` and ``q`` index
``d_k``, the SUBLANE axis of a state tile, and arrive lane-major: a head's
row is spread over the sublanes and transposed inside the kernel: 1.72 ms a
layer of ``solar2.backlog``'s arena alone on the chip, 0.26 of
``ling3.longdecode``'s, where XLA's form reads 3.30 and 0.44 (PERF.md, PR
43); handed in as ``(..., d_k, 1)`` columns they are 128 times their bytes in
HBM, and that form reads 5.53 and 0.59.

``kda_step`` is the CPU's path, the reference's, ``kda_scan``'s and this
kernel's oracle (``tests/test_kda_step_kernel.py``); :func:`kda_step_path` is
the whole rule of which one a step program takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import _pallas
from bigdl_tpu.ops._pallas import sublane_tile
from bigdl_tpu.ops.latent_attention import LANES

#: heads of one slot a grid step owns: 8 heads of 128 x 128 float32 are 512
#: KiB, 2 MiB with both directions double-buffered (16 and 32 a step read 2%
#: faster at Solar's arena and 1-3% at Ling's; the heads in a loop and not
#: unrolled a quarter slower: PERF.md, PR 43)
HEADS_BLOCK = 8


def kda_step_path(heads: int, d_k: int, d_v: int) -> str:
    """Which form a recurrent layer's decode step takes, from what is known
    when the step program is traced: the platform and the state's static
    shape.  ``"kernel"`` (:func:`kda_step_rows`) on a TPU where a head's
    state is whole tiles -- ``d_v`` whole 128-lane tiles, ``d_k`` whole
    sublane tiles -- and the heads divide into blocks of
    :data:`HEADS_BLOCK`; ``"xla"`` (``nn.kda.kda_step``, a ``select`` and a
    ``dynamic-update-slice``) for everything else: the CPU, and a shape the
    compiled kernel cannot tile."""
    if _pallas.use_interpret():
        return "xla"
    tiled = not (d_v % LANES or d_k % sublane_tile(jnp.float32)
                 or heads % HEADS_BLOCK)
    return "kernel" if tiled else "xla"


def active_slots(active):
    """What the kernel prefetches, from ``active`` (S,) bool: the active
    slots in order (behind them the idle ones, never visited) and how many
    they are."""
    ids = jnp.argsort(jnp.logical_not(active), stable=True)
    return ids.astype(jnp.int32), jnp.sum(active, dtype=jnp.int32).reshape(1)


def _kernel(layer_ref, ids_ref, count_ref, q_ref, k_ref, eg_ref, v_ref,
            beta_ref, s_ref, o_ref, s_out_ref):
    del layer_ref, ids_ref
    i, j = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    d_k, d_v = s_ref.shape[-2:]

    def rows(x):
        # a head's (1, d_k) row to the (d_k, d_v) tile whose row d holds x[d]:
        # spread over the sublanes (cheap), then transposed
        return jnp.broadcast_to(x, (d_v, d_k)).T

    @pl.when(i < count)
    def _():
        for h in range(HEADS_BLOCK):
            at = pl.ds(h, 1)
            eg, k, q = (rows(r[0, at, :]) for r in (eg_ref, k_ref, q_ref))
            decayed = s_ref[0, 0, h] * eg
            seen = jnp.sum(decayed * k, axis=0, keepdims=True)
            new = decayed + k * (beta_ref[0, at, :] * (v_ref[0, at, :] - seen))
            s_out_ref[0, 0, h] = new
            o_ref[0, at, :] = jnp.sum(new * q, axis=0, keepdims=True)

    # no slot decodes: every step maps to ONE block, which leaves as it came
    @pl.when((count == 0) & (i == 0) & (j == 0))
    def _():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_step_rows(state, layer, active, q, k, v, g, beta, *, interpret=None):
    """One position of the recurrence for the ACTIVE slots of one layer of
    the state arena, in place: ``state`` (R, S, H, d_k, d_v) float32 (donate
    it: the call aliases it to its output), ``layer`` the (traced) layer's
    index in it, ``active`` (S,) bool, ``q``, ``k``, ``g`` (S, H, d_k), ``v``
    (S, H, d_v), ``beta`` (S, H).  -> (o (S, H, d_v) float32 -- zeros for a
    slot that is not active --, the arena with the active slots' rows of
    ``layer`` advanced and every other row untouched)."""
    _, s, h, d_k, d_v = state.shape
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, got {state.dtype}")
    if h % HEADS_BLOCK:
        raise ValueError(f"{h} heads do not divide into blocks of {HEADS_BLOCK}")
    if interpret is None:
        interpret = _pallas.use_interpret()
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    ids, count = active_slots(active)
    o, state = _call(
        jnp.asarray(layer, jnp.int32).reshape(1), ids, count, q, k,
        jnp.exp(g), v, jnp.broadcast_to(beta[..., None], v.shape), state,
        interpret=bool(interpret))
    o = jnp.where(active[:, None, None], o, 0.0) / jnp.sqrt(jnp.float32(d_k))
    return o, state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(layer, ids, count, q, k, eg, v, beta, state, *, interpret):
    """The kernel's call, a jitted function of its own: a step program's
    recurrent layers are traced and lowered ONCE
    (``ops.grouped_attention._attend``)."""
    _, s, h, d_k, d_v = state.shape
    hb = HEADS_BLOCK
    last = h // hb - 1

    def slot(i, j, layer, ids, count):
        # a step past the active count stays on the last block visited: its
        # tiles are not fetched again and nothing of it is written twice
        live = i < count[0]
        at = ids[jnp.minimum(i, jnp.maximum(count[0] - 1, 0))]
        return at, jnp.where(live, j, last)

    def vector(i, j, *scalars):
        return slot(i, j, *scalars) + (0,)

    def tile(i, j, layer, ids, count):
        return (layer[0],) + slot(i, j, layer, ids, count) + (0, 0)

    by_k = pl.BlockSpec((1, hb, d_k), vector)
    by_v = pl.BlockSpec((1, hb, d_v), vector)
    tiles = pl.BlockSpec((1, 1, hb, d_k, d_v), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, h // hb),
        in_specs=[by_k, by_k, by_k, by_v, by_v, tiles],
        out_specs=[by_v, tiles])
    # the state's tile in both directions, twice each (the pipeline's
    # buffers), a head's tile a few times over for what the step holds live
    vmem = 4 * hb * d_k * d_v * 4 + 16 * d_k * d_v * 4 + (8 << 20)
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, h, d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="kda_step",
    )(layer, ids, count, q, k, eg, v, beta, state)
