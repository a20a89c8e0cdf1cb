"""Absorbed latent decode attention as a Pallas TPU kernel.

A latent pool (``serving.kvcache.blocks``, ``BlockPool(latent=True)``)
caches ONE row a position, ``[c ; k_r]`` padded to whole lanes, and a
decode step's absorbed queries -- every head's, ``W_uk`` folded in
(``TransformerLM.mla_absorb``) -- meet that row itself: the row is the key
AND, by its leading lanes, the value.  The XLA path
(``generate._paged_attention(v=None)``) walks the round's live list a chunk
at a time: a gather of the chunk's blocks, two grouped matmuls, the softmax's
passes, every stage through HBM.  This kernel reads the listed blocks WHERE
THEY LIE: the arena stays in HBM, whole (``memory_space=ANY``, the layer a
scalar-prefetch operand: a slice at the call site would copy the arena), a
grid step copies ``blocks_per_step`` blocks of one slot into VMEM with
hand-issued asynchronous copies -- a block is one contiguous ``(block_len,
lanes)`` tile -- while the step before computes on the other buffer, and the
softmax's three parts (maximum, sum, weighted rows) stay in VMEM from a
slot's first block to its last.  Nothing of a chunk is written to HBM.

**What names the blocks**: the ``(S, table_width)`` tables that
``generate._decode_step_paged``'s ``paged_kernel`` branch spells from the
live list, with ``lengths`` (S,), the positions a slot attends (0 for an idle
slot).  Tables and not the list's own runs by owner: a step's
``blocks_per_step`` entries are then the slot's own or scratch padding by
construction -- a run would be read into the next owner's -- every index is
in bounds, and the scatter that spells them is one small XLA operation.

The grid is ``(S, ceil(table_width / blocks_per_step))``, the second axis
sequential; a step past a slot's last listed block does nothing, so the bytes
read follow the live blocks to within ``blocks_per_step`` a slot.  A step's
math is the walk's, letter for letter (the walk stays the CPU path and this
kernel's oracle): bfloat16 rows against the queries in three bfloat16 pieces with
float32 accumulation (exact products), the pieces summed, ``/
sqrt(score_dim)``, ``-1e30`` past the slot's last position, an online softmax
a head in float32, the weights again in three pieces against the SAME rows in
VMEM, a float32 accumulator; float32 rows meet float32 operands at the highest
precision.  What differs from the walk is the order of the float32 sums.

Queries and weights are split into their pieces IN the kernel.  Split in XLA
(``generate._pieces``) they are exact on the CPU and NOT on the chip: the TPU
compiler drops the ``f32 -> bf16 -> f32`` casts that make a piece as excess
precision, so the second and third pieces are zeros there and a walk's
products are one bfloat16 piece's (2e-3 of the output against float64 where
this kernel reads 1e-6: PERF.md, PR 36).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import _pallas

#: blocks a grid step fetches at once (PERF.md, PR 36: the cell's reading)
BLOCKS_PER_STEP = 128
LANES = 128


def _pieces(x, n: int):
    """f32 ``x`` as ``n`` bfloat16 pieces stacked along the ROWS (``n`` = 3
    sums back to ``x`` to f32 round-off: ``generate._pieces``' arithmetic, for
    use inside the kernel), or ``x`` itself where ``n`` is 1 (float32 rows)."""
    if n == 1:
        return x
    out, rest = [], x
    for _ in range(n):
        piece = rest.astype(jnp.bfloat16)
        out.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return jnp.concatenate(out, axis=-2)


def _summed(x, n: int):
    """The inverse read: the ``n`` row groups of a product added up."""
    h = x.shape[0] // n
    out = x[:h]
    for p in range(1, n):
        out = out + x[p * h:(p + 1) * h]
    return out


def _latent_kernel(tbl_ref, len_ref, layer_ref, q_ref, arena_ref, o_ref,
                   buf, sem, q_scr, top_scr, den_scr, acc_scr, *, fetch: int,
                   block_len: int, score_dim: int, pieces: int,
                   n_rows: int = 1, first: int = 0):
    s, j = pl.program_id(0), pl.program_id(1)
    span = fetch * block_len                    # positions a step holds
    length = len_ref[s]
    if n_rows == 1:
        steps = (length + span - 1) // span     # of this slot; 0 when idle
    else:
        # a slot's last query row sees ``n_rows - 1`` positions further
        steps = jnp.where(length > 0,
                          (length + n_rows - 1 + span - 1) // span, 0)
    precision = lax.Precision.HIGHEST if pieces == 1 else None

    def copies(step, slot):
        # the step's blocks, one contiguous (block_len, lanes) tile each
        return [pltpu.make_async_copy(
            arena_ref.at[layer_ref[0], tbl_ref[s, step * fetch + i]],
            buf.at[slot, pl.ds(i * block_len, block_len)], sem.at[slot])
            for i in range(fetch)]

    @pl.when(j == 0)
    def _():
        q_scr[...] = _pieces(q_ref[0], pieces)
        top_scr[...] = jnp.full_like(top_scr, -1e30)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(steps > 0)
        def _():
            for c in copies(0, 0):
                c.start()

    @pl.when(j < steps)
    def _():
        slot = j % 2

        @pl.when(j + 1 < steps)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        rows = buf[slot]                                    # (span, lanes)
        if pieces > 1:
            rows = rows.astype(jnp.bfloat16)
        scores = _summed(lax.dot_general(
            q_scr[...], rows, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32), pieces)    # (H, span)
        scores = scores / jnp.sqrt(jnp.float32(score_dim))
        k_pos = j * span + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if n_rows == 1:
            seen = k_pos < length
        else:
            # query row r = head * n_rows + i is the slot's i-th position
            seen = k_pos < length + lax.rem(
                lax.broadcasted_iota(jnp.int32, scores.shape, 0), n_rows)
        if first:
            seen = seen & (k_pos >= first)
        scores = jnp.where(seen, scores, -1e30)
        top = jnp.maximum(top_scr[...], jnp.max(scores, axis=1, keepdims=True))
        old = jnp.exp(top_scr[...] - top)
        e = jnp.where(seen, jnp.exp(scores - top), 0.0)
        den_scr[...] = den_scr[...] * old + jnp.sum(e, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * old + _summed(lax.dot_general(
            _pieces(e, pieces), rows[:, :acc_scr.shape[1]],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32), pieces)    # (H, lanes)
        top_scr[...] = top

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # (a slot that holds nothing sums to 0 over 0: zeros, as the walk)
        o_ref[0] = acc_scr[...] / jnp.maximum(den_scr[...], 1e-30)


def check_latent_kernel_shapes(block_len: int, lanes: int, dtype) -> None:
    """Raise where the COMPILED kernel cannot take the pool's geometry: a
    block is copied to a row of the VMEM buffer that has to lie on the dtype's
    sublane tile (``ops._pallas.check_block_rows``), and a
    row is whole 128-lane tiles."""
    _pallas.check_block_rows(block_len, dtype)
    if lanes % LANES:
        raise ValueError(
            f"decode_attn='paged_kernel' needs a latent row of whole "
            f"{LANES}-lane tiles on TPU (got {lanes} lanes)")


def latent_decode_attention(q, arena, tables, lengths, *, score_dim: int,
                            layer=None, value_lanes=None,
                            blocks_per_step: int = BLOCKS_PER_STEP,
                            first: int = 0, interpret=None):
    """One decode step of absorbed latent attention, reading the latent rows
    in place.

    q: (S, H, W, D) or (S, H, D) float32 absorbed queries, D the row's own
    lanes (``kv_rank + rope``), W (static) the query POSITIONS a slot: 1 for
    a decode step, the candidate rows of a verify step, whose row i sees
    ``lengths + i`` positions (W = 1 is the kernel as it was, operation for
    operation); ``first`` (static): the first position any query sees (a
    prediction module's rows start at 1); arena: the latent pool's arena, whole --
    (L, N, block_len, lanes) with ``layer`` the (traced) layer to attend -- or
    one layer's (N, block_len, lanes); tables: (S, M) int32 block ids by slot
    (scratch-padded past the live prefix); lengths: (S,) int32, the positions
    a slot attends (its write position + 1; 0 for an idle slot, whose output
    is zeros); ``score_dim``: the width a score is scaled by;
    ``value_lanes``: how many leading lanes of a row are values (all of D by
    default; the rest of the output is not computed).  Returns the weighted
    rows, float32, shaped like q but ``value_lanes`` wide.
    """
    squeeze = q.ndim == 4
    rows = q.shape[2] if squeeze else 1
    heads = q.shape[1]
    # (head, position) pairs as the kernel's query rows, head-major
    q3 = (q.reshape(q.shape[0], heads * rows, q.shape[3]) if squeeze
          else q).astype(jnp.float32)
    s, h, d = q3.shape
    arena, layer = _pallas.arena_layer(arena, layer)
    blk, w = arena.shape[2:]
    if interpret is None:
        interpret = _pallas.use_interpret()
    if not interpret:
        check_latent_kernel_shapes(blk, w, arena.dtype)
    value_lanes = d if value_lanes is None else int(value_lanes)
    out_w = min(-(-value_lanes // LANES) * LANES, w)
    fetch = max(1, min(int(blocks_per_step), tables.shape[1]))
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, -tables.shape[1] % fetch)))
    pieces = 1 if arena.dtype == jnp.float32 else 3
    q3 = jnp.pad(q3, ((0, 0), (0, 0), (0, w - d)))

    def slot(si, ji, tbl, lens, layer):
        return (si, 0, 0)

    span = fetch * blk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, tables.shape[1] // fetch),
        in_specs=[
            pl.BlockSpec((1, h, w), slot),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, out_w), slot),
        scratch_shapes=[
            pltpu.VMEM((2, span, w), arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            # the queries' pieces side by side as rows, split IN the kernel
            # (in XLA the TPU compiler drops the casts that make a piece)
            pltpu.VMEM((pieces * h, w),
                       jnp.float32 if pieces == 1 else jnp.bfloat16),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, out_w), jnp.float32),
        ])
    kernel = functools.partial(_latent_kernel, fetch=fetch, block_len=blk,
                               score_dim=score_dim, pieces=pieces, n_rows=rows,
                               first=int(first))
    # the two fetch buffers (and, float32 rows, the pieces the highest
    # precision splits a step's rows into), a step's scores, weights and their
    # pieces (pieces * H, span) a few times over, the queries, the accumulators
    vmem = ((2 + 3 * (pieces == 1)) * span * w * arena.dtype.itemsize
            + 8 * 3 * h * span * 4 + 6 * 3 * h * w * 4 + (8 << 20))
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, out_w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="latent_decode_attention",
    )(tables, lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q3, arena)
    o = o[:, :, :value_lanes]
    return o.reshape(s, heads, rows, value_lanes) if squeeze else o
