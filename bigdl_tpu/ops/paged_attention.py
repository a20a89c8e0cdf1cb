"""Paged decode attention as a Pallas TPU kernel.

``LMServingEngine``'s decode step originally gathered every slot's KV
blocks into a dense (S, H, ctx, D) view (``kc[tables]``) before a plain
einsum attention — correct and fixed-shape, but it materializes and
copies the whole context window per token step (the ~2x decode tax in
BENCH_LM_SERVE.json).  This kernel reads the KV blocks IN PLACE: the
block table is a scalar-prefetch operand, so the BlockSpec index maps
name the arena block to stream into VMEM per grid step (the vLLM
paged-attention shape) and nothing dense is ever built.

Grid is (S, H, M) with the table column innermost: each step copies one
(block_len, D) K/V block into a per-(slot, head) VMEM context scratch,
and the last column computes the attention row with EXACTLY the dense
path's formulation — f32 scores, ``/ sqrt(D)``, ``-1e30`` mask at
positions past ``pos``, ``jax.nn.softmax``, f32 value matmul — so
greedy and sampled token streams stay token-exact with the gather
fallback (which stays selectable; see ``paged_decode_attention_reference``).

Decode works on one query token per slot, so there is no online-softmax
accumulation and no (T, T) tile: VMEM holds one (ctx, D) K and V copy
per (slot, head) program, bounded by ``cache_len``, not batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  k_scr, v_scr, *, block_len: int, ctx: int,
                  head_dim: int):
    s = pl.program_id(0)
    m = pl.program_id(2)
    n_m = pl.num_programs(2)
    # block_len is a multiple of the sublane tile (checked by the
    # wrapper when compiling), so the hint makes the dynamic store aligned
    row = pl.multiple_of(m * block_len, block_len)
    k_scr[pl.ds(row, block_len), :] = k_ref[0, 0]
    v_scr[pl.ds(row, block_len), :] = v_ref[0, 0]

    @pl.when(m == n_m - 1)
    def _():
        # the dense-gather math verbatim (f32 end to end) so the kernel
        # and the fallback produce token-identical streams
        q = q_ref[0, 0].astype(jnp.float32)                   # (1, D)
        kk = k_scr[:].astype(jnp.float32)                     # (ctx, D)
        scores = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, ctx), 1)
        scores = jnp.where(k_pos <= pos_ref[s], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        o_ref[0, 0] = jax.lax.dot_general(
            w, v_scr[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


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


def paged_decode_attention(q, k_arena, v_arena, tables, pos, *,
                           interpret=None):
    """One decode step of paged attention, reading KV blocks in place.

    q: (S, H, 1, D) or (S, H, D) query for the current token of each
    slot; k_arena/v_arena: (N, H, block_len, D) block pools; tables:
    (S, M) int32 per-slot block ids (scratch-padded past the live
    prefix); pos: (S,) int32 current position of each slot.  Returns
    f32 attention output shaped like q.
    """
    squeeze = q.ndim == 4
    # (S, H, 1, D): the two tiled (last) dims of every q/o block are the
    # array's own, which is what Mosaic's block-shape rule asks for
    q4 = q if squeeze else q[:, :, None, :]
    s, h, _, d = q4.shape
    n, _, blk, _ = k_arena.shape
    m = tables.shape[1]
    ctx = m * blk
    if interpret is None:
        interpret = _use_interpret()
    if not interpret:
        check_paged_kernel_shapes(blk, k_arena.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, h, m),  # table column innermost: scratch fills over it
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda si, hi, mi, tbl, pos: (si, hi, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda si, hi, mi, tbl, pos:
                         (tbl[si, mi], hi, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda si, hi, mi, tbl, pos:
                         (tbl[si, mi], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda si, hi, mi, tbl, pos: (si, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ctx, d), k_arena.dtype),
            pltpu.VMEM((ctx, d), v_arena.dtype),
        ])
    kernel = functools.partial(_paged_kernel, block_len=blk, ctx=ctx,
                               head_dim=d)
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, 1, d), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), q4, k_arena,
      v_arena)
    return o if squeeze else o[:, :, 0, :]


def paged_decode_attention_reference(q, k_arena, v_arena, tables, pos):
    """The dense-gather fallback: materialize kc[tables] and run the
    plain einsum attention.  This is the decode path's original math and
    the correctness/crossover oracle for the kernel above."""
    squeeze = q.ndim == 4
    q4 = q if squeeze else q[:, :, None, :]
    s, m = tables.shape
    blk = k_arena.shape[2]
    ctx = m * blk
    h, d = q4.shape[1], q4.shape[3]
    mask = (jnp.arange(ctx)[None, :] <= pos[:, None])[:, None, None, :]
    kg = k_arena[tables].transpose(0, 2, 1, 3, 4).reshape(s, h, ctx, d)
    vg = v_arena[tables].transpose(0, 2, 1, 3, 4).reshape(s, h, ctx, d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q4.astype(jnp.float32),
                        kg.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, vg.astype(jnp.float32))
    return o if squeeze else o[:, :, 0, :]
