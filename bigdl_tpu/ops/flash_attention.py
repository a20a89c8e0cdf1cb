"""Flash attention as a Pallas TPU kernel.

Forward is a tiled online-softmax kernel over a (B, H, n_q, n_k) grid: the
innermost grid dimension streams (block_k, d) K/V tiles from HBM through
VMEM while per-q-block accumulators (acc, m, l) live in VMEM scratch, so
neither the (T, T) score matrix nor the full K/V ever needs to be resident
— sequence length is bounded by HBM, not VMEM.  Causal and padded key
blocks are skipped with predicated execution.  Backward is the same tiled
recomputation as two Pallas kernels (dk/dv accumulated over query blocks;
dq accumulated over key blocks) from the saved logsumexp — like the
forward, nothing of size (T, T) is ever materialized, so long-context
training is HBM-bound too (an XLA einsum backward would OOM exactly where
flash attention is supposed to win).

Cross-attention (Tq != Tk) aligns causality bottom-right (query i attends
key j iff j - Tk <= i - Tq), matching ``dot_product_attention``.

Capability-gap fill: the reference predates attention entirely
(SURVEY.md §5.7); this is the single-chip hot path under
``MultiHeadAttention`` and composes with the ring/Ulysses sequence
parallelism in ``bigdl_tpu.parallel.sequence``.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import _pallas

_NEG = -1e30  # large-negative mask value: avoids (-inf) - (-inf) NaNs
_LANES = 128  # m/l scratch is kept lane-replicated for TPU-friendly tiles


def _fwd_kernel(*refs, scale: float, causal: bool, segmented: bool,
                tq_real: int, tk_real: int, block_q: int, block_k: int,
                window: Optional[int] = None):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        sq_ref = sk_ref = None
    iq = pl.program_id(2)
    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    d = q_ref.shape[3]

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)
        m_ref[:] = jnp.full((block_q, _LANES), _NEG, jnp.float32)
        l_ref[:] = jnp.zeros((block_q, _LANES), jnp.float32)

    # bottom-right causal alignment: query row r has global causal
    # position iq*block_q + r + (tk_real - tq_real)
    q_end = iq * block_q + block_q - 1 + (tk_real - tq_real)
    block_live = jnp.logical_and(
        j * block_k < tk_real,                      # not pure key padding
        jnp.logical_or(not causal, j * block_k <= q_end))
    if window is not None:
        # the tile's last key is still inside the FIRST query row's window
        # (the tile is fetched either way; its arithmetic is skipped)
        block_live = jnp.logical_and(
            block_live,
            j * block_k + block_k - 1 > q_end - block_q + 1 - window)

    @pl.when(block_live)
    def _():
        # matmul operands stay in the INPUT dtype (bf16 runs the MXU at
        # full rate; upcasting first would halve it) with f32
        # accumulation via preferred_element_type; softmax math is f32
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < tk_real
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + (tk_real - tq_real)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
            if window is not None:      # sliding: i - j < window
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
        if segmented:
            # packed-document isolation: a query attends only within its
            # own segment (pad fills -1/-2 can never match)
            # (sq is a (block_q, 1) column, sk a (1, block_k) row)
            mask = jnp.logical_and(mask, sq_ref[0] == sk_ref[0])
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, (block_q, _LANES))
        l_ref[:] = jnp.broadcast_to(l_new, (block_q, _LANES))

    @pl.when(j == n_k - 1)
    def _():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # lse leaves as a (1, block_q) ROW of the (B, H, 1, Tq) output:
        # one transpose of the lane-replicated (block_q, 128) statistic
        # per query block, instead of a 1-D store Mosaic cannot lay out
        l_all = l_ref[:]
        lse = m_ref[:] + jnp.log(jnp.where(l_all == 0.0, 1.0, l_all))
        lse_ref[0, 0] = lse.T[:1].astype(jnp.float32)


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying-over-
    mesh-axes sets (the output varies over any axis ANY input varies
    over — e.g. replicated q with sequence-sharded k/v), so the kernel
    works inside shard_map (check_vma) and outside it."""
    vma = frozenset()
    for x in like:
        vma = vma | (jax.typeof(x).vma or frozenset())
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_t(x, block):
    t = x.shape[2]
    rem = t % block
    if rem == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, 0), (0, block - rem), (0, 0)])


def _pad_seg(seg, block, fill):
    """Pad (B, T) segment ids to a block multiple with a fill that can
    never equal a real id on the other side (-1 vs -2)."""
    t = seg.shape[1]
    rem = t % block
    if rem == 0:
        return seg
    return jnp.pad(seg, [(0, 0), (0, block - rem)], constant_values=fill)


def _check_compiled_blocks(block_q: int, block_k: int) -> None:
    """The compiled (Mosaic) kernels tile per-row statistics and segment
    ids along LANES, so both block sizes must be multiples of 128; the
    interpreter takes any size (the tests' small tiles)."""
    if block_q % _LANES or block_k % _LANES:
        raise ValueError(
            f"flash attention on TPU needs block_q and block_k to be "
            f"multiples of {_LANES} (got block_q={block_q}, "
            f"block_k={block_k})")


# Layout of the small per-row operands (what Mosaic's block-shape rule
# allows: the last two block dims are the array's own or 8/128-aligned):
#   a ROW    is (..., 1, T) blocked (..., 1, block): lane-major, no padding
#   a COLUMN is (..., T, 1) blocked (..., block, 1): one value per sublane
# lse / delta travel as rows (they are H x T f32, a column layout would
# pad each value to a 128-lane tile in HBM); segment ids travel in
# whichever form the kernel's score orientation broadcasts directly.


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q, block_k,
               interpret, window=None):
    """``k``/``v`` may hold fewer heads than ``q`` (grouped-query
    attention): query head i reads K/V head ``i // (H / H_kv)``, through
    the tiles' index map -- no repeated heads in HBM."""
    if not interpret:
        _check_compiled_blocks(block_q, block_k)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    group = h // k.shape[1]
    segmented = seg_q is not None
    qp = _pad_t(q, block_q)
    kp = _pad_t(k, block_k)
    vp = _pad_t(v, block_k)
    tq_pad, tk_pad = qp.shape[2], kp.shape[2]
    n_q, n_k = tq_pad // block_q, tk_pad // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, segmented=segmented,
        tq_real=tq, tk_real=tk, block_q=block_q, block_k=block_k,
        window=window)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
    ]
    operands = [qp, kp, vp]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, block_q, 1),             # query ids: column
                         lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_k),             # key ids: row
                         lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        operands += [
            _pad_seg(seg_q.astype(jnp.int32), block_q, -1)[:, :, None],
            _pad_seg(seg_k.astype(jnp.int32), block_k, -2)[:, None, :]]
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),  # j innermost: scratch accumulates over it
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            _sds((b, h, tq_pad, d), q.dtype, q, k, v),
            _sds((b, h, 1, tq_pad), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)
    return o[:, :, :tq], lse[:, :, 0, :tq]


def _bwd_probs_t(q, kb, lse_row, sq_ref, sk_ref, *, scale, causal,
                 segmented, tq_real, tk_real, q0, k0, block_q, block_k):
    """Recompute the TRANSPOSED probability tile p^T (block_k, block_q)
    from the saved logsumexp.  Keys on sublanes, queries on lanes: the
    per-query scalars (lse, delta) arrive as (1, block_q) rows and
    broadcast down the sublanes with no relayout."""
    st = jax.lax.dot_general(
        kb, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    k_pos = k0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    q_pos = q0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    mask = jnp.logical_and(q_pos < tq_real, k_pos < tk_real)
    if causal:
        mask = jnp.logical_and(mask, q_pos + (tk_real - tq_real) >= k_pos)
    if segmented:
        # sk is a (block_k, 1) column, sq a (1, block_q) row
        mask = jnp.logical_and(mask, sk_ref[0] == sq_ref[0])
    return jnp.where(mask, jnp.exp(st - lse_row), 0.0)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, segmented: bool,
                    tq_real: int, tk_real: int,
                    block_q: int, block_k: int):
    """Grid (B, H, n_k, n_q), query blocks innermost: one (block_k, d)
    dk/dv pair accumulates in VMEM scratch while (block_q, d) q/do tiles
    stream past — the mirror image of the forward's streaming direction."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, rest_ref,
         sq_ref, sk_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, rest_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        sq_ref = sk_ref = None
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    n_q = pl.num_programs(3)
    d = q_ref.shape[3]

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

    q_end = iq * block_q + block_q - 1 + (tk_real - tq_real)
    block_live = jnp.logical_and(
        jnp.logical_and(ik * block_k < tk_real,   # not pure key padding
                        iq * block_q < tq_real),  # not pure query padding
        jnp.logical_or(not causal, q_end >= ik * block_k))

    @pl.when(block_live)
    def _():
        # bf16 matmul operands + f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        pt = _bwd_probs_t(
            q, kb, lse_ref[0, 0], sq_ref, sk_ref, scale=scale,
            causal=causal, segmented=segmented, tq_real=tq_real,
            tk_real=tk_real, q0=iq * block_q, k0=ik * block_k,
            block_q=block_q, block_k=block_k)
        dv_acc[:] += jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            vb, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - rest_ref[0, 0])
        dk_acc[:] += jnp.dot(dst.astype(q.dtype), q,
                             preferred_element_type=jnp.float32) * scale

    @pl.when(iq == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale: float, causal: bool, segmented: bool,
                   tq_real: int, tk_real: int,
                   block_q: int, block_k: int):
    """Grid (B, H, n_q, n_k), key blocks innermost: dq for one query block
    accumulates in scratch while K/V tiles stream past (same streaming
    direction as the forward)."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, rest_ref,
         sq_ref, sk_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, rest_ref,
         dq_ref, dq_acc) = refs
        sq_ref = sk_ref = None
    iq = pl.program_id(2)
    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    d = q_ref.shape[3]

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros((block_q, d), jnp.float32)

    q_end = iq * block_q + block_q - 1 + (tk_real - tq_real)
    block_live = jnp.logical_and(
        jnp.logical_and(j * block_k < tk_real, iq * block_q < tq_real),
        jnp.logical_or(not causal, j * block_k <= q_end))

    @pl.when(block_live)
    def _():
        # bf16 matmul operands + f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        pt = _bwd_probs_t(
            q, kb, lse_ref[0, 0], sq_ref, sk_ref, scale=scale,
            causal=causal, segmented=segmented, tq_real=tq_real,
            tk_real=tk_real, q0=iq * block_q, k0=j * block_k,
            block_q=block_q, block_k=block_k)
        dpt = jax.lax.dot_general(
            vb, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - rest_ref[0, 0])
        # dq = ds @ k with ds = dst^T: contract the key (sublane) axis
        dq_acc[:] += jax.lax.dot_general(
            dst.astype(kb.dtype), kb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _row(x, block):
    """(B, H, T) per-query scalars -> block-padded (B, H, 1, T) rows."""
    rem = x.shape[2] % block
    if rem:
        x = jnp.pad(x, [(0, 0), (0, 0), (0, block - rem)])
    return x[:, :, None, :]


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_bwd(q, k, v, o, lse, do, dlse, seg_q, seg_k, causal, scale,
               block_q, block_k, interpret):
    """Tiled backward: dq, dk, dv with nothing of size (Tq, Tk) resident.
    ``delta = rowsum(do * o)`` is the standard flash backward scalar; the
    optional lse cotangent folds in as ``ds += p * dlse``."""
    if not interpret:
        _check_compiled_blocks(block_q, block_k)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    segmented = seg_q is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp, dop = _pad_t(q, block_q), _pad_t(do, block_q)
    kp, vp = _pad_t(k, block_k), _pad_t(v, block_k)
    lsep = _row(lse, block_q)
    restp = _row(delta - dlse.astype(jnp.float32), block_q)
    tq_pad, tk_pad = qp.shape[2], kp.shape[2]
    n_q, n_k = tq_pad // block_q, tk_pad // block_k
    seg_operands = []
    if segmented:
        seg_operands = [
            _pad_seg(seg_q.astype(jnp.int32), block_q, -1)[:, None, :],
            _pad_seg(seg_k.astype(jnp.int32), block_k, -2)[:, :, None]]
    operands = [qp, kp, vp, dop, lsep, restp] + seg_operands
    kw = dict(scale=scale, causal=causal, segmented=segmented,
              tq_real=tq, tk_real=tk, block_q=block_q, block_k=block_k)

    def specs(qi_of, ki_of):
        """Input specs for a grid (b, h, outer, inner); ``qi_of`` /
        ``ki_of`` pick the query / key block index out of (outer, inner)."""
        qspec = pl.BlockSpec(
            (1, 1, block_q, d),
            lambda bi, hi, oi, ii: (bi, hi, qi_of(oi, ii), 0))
        kspec = pl.BlockSpec(
            (1, 1, block_k, d),
            lambda bi, hi, oi, ii: (bi, hi, ki_of(oi, ii), 0))
        rowspec = pl.BlockSpec(
            (1, 1, 1, block_q),
            lambda bi, hi, oi, ii: (bi, hi, 0, qi_of(oi, ii)))
        out = [qspec, kspec, kspec, qspec, rowspec, rowspec]
        if segmented:
            out += [
                pl.BlockSpec((1, 1, block_q),         # query ids: row
                             lambda bi, hi, oi, ii: (bi, 0, qi_of(oi, ii))),
                pl.BlockSpec((1, block_k, 1),         # key ids: column
                             lambda bi, hi, oi, ii: (bi, ki_of(oi, ii), 0)),
            ]
        return out

    outer = lambda oi, ii: oi  # noqa: E731
    inner = lambda oi, ii: ii  # noqa: E731
    kv_out = pl.BlockSpec((1, 1, block_k, d),
                          lambda bi, hi, oi, ii: (bi, hi, oi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(b, h, n_k, n_q),  # query blocks innermost
        in_specs=specs(qi_of=inner, ki_of=outer),
        out_specs=[kv_out, kv_out],
        out_shape=[
            _sds((b, h, tk_pad, d), k.dtype, q, k, v, do),
            _sds((b, h, tk_pad, d), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*operands)

    (dq,) = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(b, h, n_q, n_k),  # key blocks innermost
        in_specs=specs(qi_of=outer, ki_of=inner),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, oi, ii: (bi, hi, oi, 0)),
        ],
        out_shape=[_sds((b, h, tq_pad, d), q.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*operands)
    return dq[:, :, :tq], dk[:, :, :tk], dv[:, :, :tk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, seg_q, seg_k, causal, scale, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                      block_k, _pallas.use_interpret())
    return o


def _flash_vjp_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                        block_k, _pallas.use_interpret())
    return o, (q, k, v, seg_q, seg_k, o, lse)


def _flash_bwd_reference(causal, scale, res, do, dlse=None):
    """O(Tq*Tk) XLA recomputation backward — kept ONLY as the correctness
    oracle for the tiled kernel (tests compare the two); the VJPs below use
    the Pallas ``_flash_bwd``.  With ``dlse`` (the cotangent of the
    logsumexp output): d lse_i / d s_ij = p_ij, so it adds ``p * dlse`` to
    the score cotangent."""
    q, k, v, o, lse = res
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    do32, o32 = do.astype(jnp.float32), o.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
    if causal:  # bottom-right alignment, same as the forward kernel
        tq, tk = q.shape[2], k.shape[2]
        cmask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        # mask p explicitly: a fully-masked row has lse = _NEG and
        # exp(_NEG - _NEG) = 1 would resurrect every masked key
        p = jnp.where(cmask, jnp.exp(s - lse[..., None]), 0.0)
    else:
        p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32, v32)
    delta = jnp.sum(do32 * o32, axis=-1)
    ds = p * (dp - delta[..., None])
    if dlse is not None:
        ds = ds + p * dlse.astype(jnp.float32)[..., None]
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k32) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, seg_q, seg_k, o, lse = res
    dlse = jnp.zeros(lse.shape, jnp.float32)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, dlse, seg_q, seg_k,
                            causal, scale, block_q, block_k,
                            _pallas.use_interpret())
    return dq, dk, dv, None, None  # int segment ids carry no cotangent


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, seg_q, seg_k, causal, scale, block_q, block_k):
    return _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                      block_k, _pallas.use_interpret())


def _flash_lse_vjp_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                       block_k):
    o, lse = _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                        block_k, _pallas.use_interpret())
    return (o, lse), (q, k, v, seg_q, seg_k, o, lse)


def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, res, cts):
    do, dlse = cts
    q, k, v, seg_q, seg_k, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, dlse, seg_q, seg_k,
                            causal, scale, block_q, block_k,
                            _pallas.use_interpret())
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


#: sequence length at which MultiHeadAttention's "auto" mode switches
#: from XLA's fused attention to the Pallas flash kernel on TPU.  Below
#: the crossover XLA's single fused kernel wins (no pallas_call launch
#: framing, and the (T,T) scores still fit VMEM-friendly fusions); above
#: it the flash tiles win on HBM traffic and, past ~8-16k, are the only
#: thing that fits at all.  Override with BIGDL_TPU_FLASH_MIN_T.
FLASH_AUTO_MIN_T = int(os.environ.get("BIGDL_TPU_FLASH_MIN_T", "4096"))


def use_flash_auto(seq_len: int) -> bool:
    """The "auto" dispatch rule: Pallas flash iff running on a real TPU
    backend AND the sequence is past the crossover (interpreter-mode
    flash on CPU is a correctness tool, never a speed win)."""
    return jax.default_backend() == "tpu" and seq_len >= FLASH_AUTO_MIN_T


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """Tiled flash attention.  q: (B, H, Tq, D); k, v: (B, H, Tk, D) — D
    should be a multiple of 128 for MXU-aligned tiles (smaller D works at
    reduced efficiency).  Runs the Pallas kernel on TPU, interpreter mode
    elsewhere; differentiable via the recomputation backward.

    Block sizes left as None are 128 x 128.

    ``segment_ids`` (B, T) int: packed-document isolation for
    self-attention — position i attends position j only when their ids
    match (on top of causality), so documents packed into one window
    (dataset.text.DocumentPacker) never attend across boundaries.  The
    mask is applied inside the existing tiles: no (T, T) materialization,
    same VMEM footprint.  Self-attention only (requires Tq == Tk).

    ``window`` (with ``causal``): a sliding window, query i sees key j
    iff ``0 <= i - j < window``, one more term of the tile mask.  ``k``
    and ``v`` may hold fewer heads than ``q`` (a divisor: grouped-query
    attention).  Either makes this the FORWARD kernel alone (a prefill;
    the backward kernels know neither): always the Pallas kernel, and
    not differentiable."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if segment_ids is not None and q.shape[-2] != k.shape[-2]:
        raise ValueError("segment_ids requires self-attention (Tq == Tk)")
    if window is not None or k.shape[1] != q.shape[1]:
        if window is not None and not causal:
            raise ValueError("window requires causal=True")
        if q.shape[1] % k.shape[1]:
            raise ValueError(f"{q.shape[1]} query heads do not divide over "
                             f"{k.shape[1]} K/V heads")
        o, _ = _flash_fwd(q, k, v, segment_ids, segment_ids, causal,
                          float(scale), int(block_q or 128),
                          int(block_k or 128), _pallas.use_interpret(),
                          None if window is None else int(window))
        return o
    return _flash(q, k, v, segment_ids, segment_ids, causal, float(scale),
                  int(block_q or 128), int(block_k or 128))


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             q_segment_ids=None, kv_segment_ids=None,
                             block_q: int = 128, block_k: int = 128):
    """Flash attention that also returns the logsumexp (B, H, Tq) of the
    scaled scores.  Two partial results over disjoint key sets merge
    exactly via logsumexp weighting::

        lse = logaddexp(lse_a, lse_b)
        o   = o_a * exp(lse_a - lse) + o_b * exp(lse_b - lse)

    which is how ``bigdl_tpu.parallel.sequence`` composes this kernel
    into ring attention (each ring hop contributes one (o, lse) pair).
    Fully-masked rows report lse ~ -1e30 and o = 0, the identity of that
    merge.  Differentiable: the lse cotangent folds into the score
    cotangent as ``p * dlse`` (d lse/d s = softmax).

    ``q_segment_ids`` (B, Tq) / ``kv_segment_ids`` (B, Tk): packed-
    document isolation with INDEPENDENT sides — exactly what ring
    attention needs, where the rotating k/v shard carries a different
    slice of the global segment ids than the local queries."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("pass both q_segment_ids and kv_segment_ids "
                         "or neither")
    return _flash_lse(q, k, v, q_segment_ids, kv_segment_ids, causal,
                      float(scale), int(block_q), int(block_k))
