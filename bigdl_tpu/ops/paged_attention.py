"""Paged decode attention as a Pallas TPU kernel.

``LMServingEngine``'s decode step originally gathered every slot's KV
blocks into a dense (S, ctx, H, D) view (``read_chain``) before a plain
einsum attention — correct and fixed-shape, but it materializes and
copies the whole context window per token step.  This kernel reads the
KV blocks IN PLACE: the
block table is a scalar-prefetch operand, so the BlockSpec index maps
name the arena block to stream into VMEM per grid step (the vLLM
paged-attention shape) and nothing dense is ever built.

The arenas are the pool's (``serving.kvcache.blocks``: ``(L, N,
block_len, W)``, a position row holding its H heads side by side, padded
to whole lanes), taken WHOLE with the layer as one more scalar-prefetch
operand: a block is one contiguous ``(block_len, W)`` tile, and slicing
a layer out at the call site would copy what the layout exists to leave
alone.  Grid is (S, M) with the table column innermost: each step copies
one block — all heads of ``block_len`` positions — into a per-slot VMEM
context scratch, and the last column computes every head's attention row
at once.  Heads are column groups of a row, so the query arrives
BLOCK-DIAGONAL, ``(H, W)`` with head h's D values in h's columns and
zeros elsewhere: ``q_bd @ K^T`` over the full row width is exactly the
per-head dot (the other columns add exact zeros), no lane is sliced at
an offset that is not a tile's, and the value matmul returns ``(H, W)``
whose h-th column group of row h is head h's output (the wrapper keeps
that diagonal).  The formulation is EXACTLY the dense path's — f32
scores, ``/ sqrt(D)``, ``-1e30`` mask at positions past ``pos``,
``jax.nn.softmax``, f32 value matmul — so greedy and sampled token
streams stay token-exact with the gather fallback (which stays
selectable; see ``paged_decode_attention_reference``).

Decode works on one query token per slot, so there is no online-softmax
accumulation and no (T, T) tile: VMEM holds one (ctx, W) K and V copy
per slot program (and their f32 upcasts), bounded by ``cache_len``, not
batch; the wrapper asks the compiler for that much.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_kernel(tbl_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  k_scr, v_scr, *, block_len: int, ctx: int,
                  head_dim: int):
    s = pl.program_id(0)
    m = pl.program_id(1)
    n_m = pl.num_programs(1)
    # block_len is a multiple of the sublane tile (checked by the
    # wrapper when compiling), so the hint makes the dynamic store aligned
    row = pl.multiple_of(m * block_len, block_len)
    k_scr[pl.ds(row, block_len), :] = k_ref[0, 0]
    v_scr[pl.ds(row, block_len), :] = v_ref[0, 0]

    @pl.when(m == n_m - 1)
    def _():
        # the dense-gather math verbatim (f32 end to end) so the kernel
        # and the fallback produce token-identical streams
        q = q_ref[0]                                          # (H, W) f32
        kk = k_scr[:].astype(jnp.float32)                     # (ctx, W)
        scores = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (H, ctx)
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        k_pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(k_pos <= pos_ref[s], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        o_ref[0] = jax.lax.dot_general(
            w, v_scr[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (H, W)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def check_paged_kernel_shapes(block_len: int, dtype) -> None:
    """Raise where the COMPILED kernel cannot take the pool's geometry:
    each grid step stores one (block_len, D) block at a dynamic row of
    the context scratch, and Mosaic wants that row aligned to the
    dtype's sublane tile (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit).
    The serving engine calls this at construction so a pool the kernel
    cannot read is an error there, not a silent gather."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    if block_len % tile:
        raise ValueError(
            f"decode_attn='paged_kernel' needs block_len to be a multiple "
            f"of {tile} for {jnp.dtype(dtype).name} KV blocks on TPU "
            f"(got block_len={block_len})")


def _arena_layer(arena, layer):
    """A lone layer's arena (N, B, W) is the whole arena's layer 0."""
    if arena.ndim == 3:
        return arena[None], 0
    return arena, layer


def paged_decode_attention(q, k_arena, v_arena, tables, pos, *,
                           layer=None, interpret=None):
    """One decode step of paged attention, reading KV blocks in place.

    q: (S, H, 1, D) or (S, H, D) query for the current token of each
    slot; k_arena/v_arena: the pool's block arenas, whole —
    (L, N, block_len, W) with ``layer`` the (traced) layer to attend —
    or one layer's (N, block_len, W); tables: (S, M) int32 per-slot
    block ids (scratch-padded past the live prefix); pos: (S,) int32
    current position of each slot.  Returns f32 attention output shaped
    like q.
    """
    squeeze = q.ndim == 4
    q3 = q[:, :, 0, :] if squeeze else q
    s, h, d = q3.shape
    k_arena, layer = _arena_layer(k_arena, layer)
    v_arena, _ = _arena_layer(v_arena, layer)
    blk, w = k_arena.shape[2:]
    m = tables.shape[1]
    ctx = m * blk
    if interpret is None:
        interpret = _use_interpret()
    if not interpret:
        check_paged_kernel_shapes(blk, k_arena.dtype)
    # block-diagonal query: row h holds head h's D values in ITS columns
    # of the position row, zeros in every other head's and in the padding
    head_of_col = jnp.arange(w) // d
    q_bd = jnp.where(head_of_col[None, :] == jnp.arange(h)[:, None],
                     jnp.pad(q3.astype(jnp.float32).reshape(s, 1, h * d),
                             ((0, 0), (0, 0), (0, w - h * d))), 0.0)

    def block(si, mi, tbl, pos, layer):
        return (layer[0], tbl[si, mi], 0, 0)

    def slot(si, mi, tbl, pos, layer):
        return (si, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, m),  # table column innermost: scratch fills over it
        in_specs=[
            pl.BlockSpec((1, h, w), slot),
            pl.BlockSpec((1, 1, blk, w), block),
            pl.BlockSpec((1, 1, blk, w), block),
        ],
        out_specs=pl.BlockSpec((1, h, w), slot),
        scratch_shapes=[
            pltpu.VMEM((ctx, w), k_arena.dtype),
            pltpu.VMEM((ctx, w), v_arena.dtype),
        ])
    kernel = functools.partial(_paged_kernel, block_len=blk, ctx=ctx,
                               head_dim=d)
    # the two context scratches, their f32 upcasts and the score rows
    vmem = ctx * w * (2 * k_arena.dtype.itemsize + 3 * 4) + (8 << 20)
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_bd, k_arena, v_arena)
    # head h's output is the h-th column group of row h
    o = o[:, :, :h * d].reshape(s, h, h, d)[:, jnp.arange(h), jnp.arange(h)]
    return o[:, :, None, :] if squeeze else o


def paged_decode_attention_reference(q, k_arena, v_arena, tables, pos, *,
                                     layer=None):
    """The dense-gather fallback: materialize each slot's chain (the
    pool's ``read_chain``) and run the plain einsum attention.  This is
    the decode path's original math and the correctness/crossover oracle
    for the kernel above."""
    from bigdl_tpu.serving.kvcache.blocks import read_chain
    squeeze = q.ndim == 4
    q4 = q if squeeze else q[:, :, None, :]
    h, d = q4.shape[1], q4.shape[3]
    k_arena, layer = _arena_layer(k_arena, layer)
    v_arena, _ = _arena_layer(v_arena, layer)
    ctx = tables.shape[1] * k_arena.shape[2]
    mask = (jnp.arange(ctx)[None, :] <= pos[:, None])[:, None, None, :]
    block = (k_arena.shape[2], h, d)
    kg = read_chain(k_arena, layer, tables, block)            # (S, ctx, H, D)
    vg = read_chain(v_arena, layer, tables, block)
    scores = jnp.einsum("bhqd,bkhd->bhqk", q4.astype(jnp.float32),
                        kg.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", w, vg.astype(jnp.float32))
    return o if squeeze else o[:, :, 0, :]
