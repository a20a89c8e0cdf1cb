"""Grouped-head decode attention over a ``(k, v)`` pool as a Pallas TPU kernel.

A decode step's softmax layer (``G = n_head / n_kv_head`` query heads a K/V
head, 1 or more; a sliding window or none) reads the round's listed blocks
WHERE THEY LIE.  The XLA path (``generate._paged_attention`` through
``_attend_by_owner``) walks the live list a chunk at a time: a gather of the
chunk's K and V blocks, two grouped matmuls against the queries spread over a
row's lanes, the softmax's passes, every stage through HBM.  This kernel is
``ops.latent_attention``'s shape with two arenas and heads: the arenas stay in
HBM, whole (``memory_space=ANY``, the layer a scalar-prefetch operand), a grid
step copies ``blocks_per_step`` K blocks and V blocks of one slot into VMEM
with hand-issued asynchronous copies -- a block is one contiguous
``(block_len, lanes)`` tile, a position's ``n_kv`` heads side by side -- while
the step before computes on the other buffer, and the softmax's three parts
stay in VMEM from a slot's first block to its last.

**What names the blocks**: the ``(S, table_width)`` tables and ``lengths``
(S,) that ``generate._decode_step_paged``'s ``paged_kernel`` branch spells
(``ops.latent_attention`` says why tables and not the list's runs).

The grid is ``(S, steps)``, both axes sequential; a step past a slot's last
listed block does nothing.  A slot has only a few steps here (a context of a
thousand positions is two of 32 blocks), so the fetch of a slot's FIRST step is
issued by the slot before it, during its last: the copies' latency hides behind
compute across slots as well as inside one (``turn``: which buffer is next, and
whether it is already being filled).  Under a ``window`` a slot's first step is
the one holding ``q_pos - window + 1``, so a sliding layer reads its window's
blocks and not its chain, and the grid is as many steps as a window can touch.

A step's math is the walk's as WRITTEN (the walk stays the CPU path and this
kernel's oracle): for each K/V head -- a static loop over slices at multiples
of the head's lanes -- the head's ``G`` query rows in three bfloat16 pieces
against the step's K columns with float32 accumulation (exact products), the
pieces summed, ``/ sqrt(D)``, ``-1e30`` where unseen, an online softmax a query
row in float32, the weights again in three pieces against the V columns of the
same head, a float32 accumulator a head; float32 rows meet float32 operands at
the highest precision.  What differs from the walk is the order of the float32
sums.  Queries and weights are split into their pieces IN the kernel: split in
XLA the TPU compiler drops the casts that make a piece (PERF.md, PR 36).

**Two widths, and a sink.**  Keys and values may differ in width (``v_dim``:
the K and V arenas' rows then differ too), and a key head need not be whole
lane tiles: heads are then read ``heads_a_read`` at a time, the fewest whose
lanes together are (two heads of 192 are three tiles), against query rows
that are zero outside their own head's lanes -- the walk's ``head_columns``
inside one read -- so every slice of a row starts and ends on a tile; a value
head is whole tiles.  A layer's learned ``sink`` (one float32 logit a query
head, a column of the softmax with a probability and no value) is where a
query row's online softmax STARTS: maximum ``sink``, sum ``exp(0) = 1``, no
values -- in place of ``-1e30`` and 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import _pallas
from bigdl_tpu.ops.latent_attention import (LANES, _pieces, _summed,
                                            check_latent_kernel_shapes)

#: blocks of K and of V a grid step fetches at once: the best of a sweep inside
#: solar2.backlog's WHOLE decode step on the chip (PERF.md, PR 38: 128 slots,
#: 5,902 live blocks; 16 / 32 / 64 / 128 blocks a step: a round of 22.91 /
#: 22.51 / 22.56 / 23.20 ms where the walk's is 30.44, the kernel alone 1.39 /
#: 0.99 / 1.06 / 1.66 ms)
BLOCKS_PER_STEP = 32
#: query rows of a K/V head are padded to whole float32 sublane tiles
ROWS = 8


def _grouped_kernel(tbl_ref, len_ref, layer_ref, q_ref, sink_ref, k_ref, v_ref,
                    o_ref, kbuf, vbuf, sem, q_scr, top_scr, den_scr, acc_scr,
                    turn, *, fetch: int, block_len: int, n_kv: int,
                    head_dim: int, v_dim: int, heads_a_read: int, rows: int,
                    window, pieces: int):
    s, j = pl.program_id(0), pl.program_id(1)
    slots = pl.num_programs(0)
    span = fetch * block_len                    # positions a step holds
    precision = lax.Precision.HIGHEST if pieces == 1 else None

    def steps_of(slot):
        """(the first step a slot reads, how many): none when it is idle."""
        length = len_ref[slot]
        first = (0 if window is None
                 else jnp.maximum(length - window, 0) // span)
        return first, (length + span - 1) // span - first

    def copies(slot, step, b, act):
        # the step's blocks, one contiguous (block_len, lanes) tile each: a
        # loop and not ``fetch`` copies spelled out, which EVERY warm start
        # traces and lowers again, a call site at a time (0.5 s a call at 32
        # blocks where the loop takes 0.2; Laguna's warm set-up read +27%
        # with its five layers spelled out: PERF.md, PR 38)
        def block(i, _):
            rows = pl.ds(pl.multiple_of(i * block_len, block_len), block_len)
            for arena, buf in ((k_ref, kbuf), (v_ref, vbuf)):
                act(pltpu.make_async_copy(
                    arena.at[layer_ref[0], tbl_ref[slot, step * fetch + i]],
                    buf.at[b, rows], sem.at[b]))

        lax.fori_loop(0, fetch, block, None)

    def start(slot, step, b):
        copies(slot, step, b, lambda c: c.start())

    length = len_ref[s]
    first, steps = steps_of(s)

    @pl.when((s == 0) & (j == 0))
    def _():
        turn[0] = 0         # the buffer the next step computes on
        turn[1] = 0         # 1: the slot before has issued this slot's first

    reads = n_kv // heads_a_read

    @pl.when(j == 0)
    def _():
        for h in range(reads):
            q_scr[h] = _pieces(q_ref[0, h], pieces)
        # a row's softmax starts at its sink (-1e30 where the layer has
        # none): the sink's own mass is exp(0), and it owns no value
        top_scr[...] = sink_ref[...]
        den_scr[...] = jnp.where(sink_ref[...] > -1e29, 1.0, 0.0)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when((steps > 0) & (turn[1] == 0))
        def _():
            start(s, first, turn[0])

    @pl.when(j < steps)
    def _():
        b = turn[0]
        step = first + j

        @pl.when(j + 1 < steps)
        def _():
            start(s, step + 1, 1 - b)

        # a slot's last step issues the next slot's first fetch
        after = jnp.minimum(s + 1, slots - 1)
        after_first, after_steps = steps_of(after)
        ahead = (j + 1 == steps) & (s + 1 < slots) & (after_steps > 0)

        @pl.when(ahead)
        def _():
            start(after, after_first, 1 - b)

        turn[1] = ahead.astype(jnp.int32)
        copies(s, step, b, lambda c: c.wait())
        k_pos = step * span + lax.broadcasted_iota(jnp.int32, (1, span), 1)
        seen = k_pos < length
        if window is not None:
            seen = seen & (k_pos >= length - window)
        wide = heads_a_read * head_dim
        for h in range(reads):
            # ``heads_a_read`` key heads at once: a query row is zero outside
            # its own head's lanes, so its score is its own head's
            k = kbuf[b, :, pl.ds(h * wide, wide)]           # (span, wide)
            if pieces > 1:
                k = k.astype(jnp.bfloat16)
            scores = _summed(lax.dot_general(
                q_scr[h], k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32), pieces)  # (rows', span)
            scores = scores / jnp.sqrt(jnp.float32(head_dim))
            scores = jnp.where(seen, scores, -1e30)
            top = jnp.maximum(top_scr[h],
                              jnp.max(scores, axis=1, keepdims=True))
            old = jnp.exp(top_scr[h] - top)
            e = jnp.where(seen, jnp.exp(scores - top), 0.0)
            den_scr[h] = den_scr[h] * old + jnp.sum(e, axis=1, keepdims=True)
            for i in range(heads_a_read):
                head = h * heads_a_read + i
                v = vbuf[b, :, pl.ds(head * v_dim, v_dim)]  # (span, D_v)
                if pieces > 1:
                    v = v.astype(jnp.bfloat16)
                acc_scr[head] = acc_scr[head] * old[i * rows:(i + 1) * rows] + (
                    _summed(lax.dot_general(
                        _pieces(e[i * rows:(i + 1) * rows], pieces), v,
                        (((1,), (0,)), ((), ())), precision=precision,
                        preferred_element_type=jnp.float32), pieces))
            top_scr[h] = top
        turn[0] = 1 - b

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # (a slot that holds nothing sums to 0 over 0: zeros, as the walk)
        for head in range(n_kv):
            h, i = divmod(head, heads_a_read)
            o_ref[0, head] = acc_scr[head] / jnp.maximum(
                den_scr[h, i * rows:(i + 1) * rows], 1e-30)


def heads_a_read(head_dim: int, n_kv: int):
    """The fewest key heads whose lanes together are whole lane tiles, and
    that divide the K/V heads (1 where a head is whole tiles itself); None
    where there is no such count."""
    return next((n for n in (1, 2, 4) if not (n * head_dim) % LANES
                 and not n_kv % n), None)


def check_grouped_kernel_shapes(block_len: int, lanes: int, head_dim: int,
                                dtype, v_dim=None, n_kv=None,
                                v_lanes=None) -> None:
    """Raise where the COMPILED kernel cannot take the pool's geometry: a
    block lies on the dtype's sublane tile and a row is whole 128-lane tiles
    (``ops.latent_attention.check_latent_kernel_shapes``), and every slice of
    a row starts and ends on a lane tile: a value head is whole tiles, and so
    are the key heads of one read (:func:`heads_a_read`)."""
    check_latent_kernel_shapes(block_len, lanes, dtype)
    if v_lanes is not None:
        check_latent_kernel_shapes(block_len, v_lanes, dtype)
    v_dim = head_dim if v_dim is None else v_dim
    n_kv = n_kv if n_kv is not None else 1
    if v_dim % LANES or heads_a_read(head_dim, n_kv) is None:
        raise ValueError(
            f"decode_attn='paged_kernel' needs a head of whole {LANES}-lane "
            f"tiles on TPU (got head_dim={head_dim}, values of {v_dim}, "
            f"{n_kv} K/V heads)")


def grouped_decode_attention(q, k_arena, v_arena, tables, lengths, *,
                             layer=None, n_kv_head=None, window=None,
                             sink=None, v_dim=None,
                             blocks_per_step: int = BLOCKS_PER_STEP,
                             interpret=None):
    """One decode step of softmax attention whose query heads share K/V heads,
    reading the K/V blocks in place.

    q: (S, H, 1, D) or (S, H, D) queries, query head i reading K/V head ``i //
    (H / n_kv_head)``; k_arena/v_arena: the pool's arenas, whole -- (L, N,
    block_len, lanes) with ``layer`` the (traced) layer to attend -- or one
    layer's (N, block_len, lanes), a position's ``n_kv_head`` heads side by
    side in a row's leading lanes (``lanes // D`` of them where not given: a
    row without lane padding); tables: (S, M) int32 block ids by slot
    (scratch-padded past the live prefix); lengths: (S,) int32, the positions
    a slot attends (its write position + 1; 0 for an idle slot, whose output
    is zeros); ``window`` (static): a position is seen when ``q_pos - window <
    k_pos <= q_pos``, ``q_pos`` the slot's last; ``sink`` (H,) float32 or
    None: the layer's learned sink logits (module docstring); ``v_dim``: a
    value head's lanes where they are not a key head's (``v_arena``'s rows
    hold ``n_kv_head`` of them side by side).  Returns the attention
    output, float32, (S, H[, 1], v_dim).
    """
    squeeze = q.ndim == 4
    q3 = (q[:, :, 0, :] if squeeze else q).astype(jnp.float32)
    s, h, d = q3.shape
    dv = d if v_dim is None else int(v_dim)
    k_arena, layer = _pallas.arena_layer(k_arena, layer)
    v_arena, _ = _pallas.arena_layer(v_arena, layer)
    blk, w = k_arena.shape[2:]
    n_kv = w // d if n_kv_head is None else int(n_kv_head)
    if h % n_kv or n_kv * d > w or n_kv * dv > v_arena.shape[3]:
        raise ValueError(f"{h} query heads of {d} do not divide over {n_kv} "
                         f"K/V heads in a row of {w} lanes")
    if interpret is None:
        interpret = _pallas.use_interpret()
    if not interpret:
        check_grouped_kernel_shapes(blk, w, d, k_arena.dtype, dv, n_kv,
                                    v_arena.shape[3])
    if sink is None:
        sink = jnp.full((h,), -1e30, jnp.float32)
    o = _attend(q3, k_arena, v_arena, tables.astype(jnp.int32),
                lengths.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1),
                sink.astype(jnp.float32), n_kv=n_kv, v_dim=dv,
                window=None if window is None else int(window),
                fetch=max(1, min(int(blocks_per_step), tables.shape[1])),
                interpret=bool(interpret))
    return o[:, :, None, :] if squeeze else o


@functools.partial(jax.jit, static_argnames=("n_kv", "v_dim", "window",
                                             "fetch", "interpret"))
def _attend(q3, k_arena, v_arena, tables, lengths, layer, sink, *, n_kv,
            v_dim, window, fetch, interpret):
    """The kernel's call, a jitted function of its own: the layers of a step
    program that attend alike (a period's three sliding layers, the full ones
    of two groups) are traced and lowered ONCE, which a warm start pays again
    every time (0.2 s a call and more on a serving host)."""
    s, h, d = q3.shape
    blk, w = k_arena.shape[2:]
    wv = v_arena.shape[3]
    g = h // n_kv
    rows = -(-g // ROWS) * ROWS
    # key heads a read (whole lane tiles together; the interpreter takes any)
    hp = heads_a_read(d, n_kv) or 1
    reads = n_kv // hp
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % fetch)))
    span = fetch * blk
    steps = tables.shape[1] // fetch
    if window is not None:
        # the steps a window's positions can lie in
        steps = min(steps, -(-window // span) + 1)
    pieces = 1 if k_arena.dtype == jnp.float32 else 3
    q4 = jnp.pad(q3.reshape(s, n_kv, g, d),
                 ((0, 0), (0, 0), (0, rows - g), (0, 0)))
    if hp > 1:
        # a read's heads side by side: head i's rows hold its query at its
        # own lanes of the read and zeros at the others'
        own = jnp.eye(hp, dtype=q4.dtype)[None, None, :, None, :, None]
        q4 = (q4.reshape(s, reads, hp, rows, 1, d) * own).reshape(
            s, reads, hp * rows, hp * d)
    sink = jnp.pad(sink.reshape(n_kv, g), ((0, 0), (0, rows - g)),
                   constant_values=-1e30).reshape(reads, hp * rows, 1)

    def slot(si, ji, tbl, lens, layer):
        return (si, 0, 0, 0)

    part = pltpu.VMEM((reads, hp * rows, 1), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, steps),
        in_specs=[
            pl.BlockSpec((1, reads, hp * rows, hp * d), slot),
            pl.BlockSpec((reads, hp * rows, 1),
                         lambda si, ji, tbl, lens, layer: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_kv, rows, v_dim), slot),
        scratch_shapes=[
            pltpu.VMEM((2, span, w), k_arena.dtype),
            pltpu.VMEM((2, span, wv), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            # a read's query rows, their pieces side by side as rows, split IN
            # the kernel (in XLA the TPU compiler drops the casts that make one)
            pltpu.VMEM((reads, pieces * hp * rows, hp * d),
                       jnp.float32 if pieces == 1 else jnp.bfloat16),
            part, part,
            pltpu.VMEM((n_kv, rows, v_dim), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ])
    kernel = functools.partial(_grouped_kernel, fetch=fetch, block_len=blk,
                               n_kv=n_kv, head_dim=d, v_dim=v_dim,
                               heads_a_read=hp, rows=rows, window=window,
                               pieces=pieces)
    # the four fetch buffers, a read's K and a head's V columns (and, float32
    # rows, the pieces the highest precision splits them into), its scores,
    # weights and their pieces a few times over, the queries and the
    # accumulators
    vmem = (2 * span * (w + wv) * k_arena.dtype.itemsize
            + (2 + 6 * (pieces == 1)) * span * hp * d * 4
            + 8 * 3 * hp * rows * span * 4 + 6 * 3 * h * hp * d * 4
            + (8 << 20))
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_kv, rows, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="grouped_decode_attention",
    )(tables, lengths, layer, q4, sink, k_arena, v_arena)
    return o[:, :, :g].reshape(s, h, v_dim)
