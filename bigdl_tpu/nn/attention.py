"""Attention layers (capability-gap fill: the reference predates attention —
SURVEY.md §5.7 — so long-context support is designed TPU-first rather than
ported: batched (B, H, T, D) matmuls for the MXU, online-softmax blockwise
streaming for HBM, and a ring/sequence-parallel path in
``bigdl_tpu.parallel.sequence``).

API follows the house style: modules are (B, T, F) like Recurrent
(ref nn/Recurrent.scala batch x time x feature layout).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn._util import match_compute_dtype

NEG_INF = float("-inf")


def _safe_exp(x, m):
    """exp(x - m) with -inf maxima treated as empty (0 weight)."""
    return jnp.where(jnp.isneginf(m), 0.0, jnp.exp(x - jnp.where(
        jnp.isneginf(m), 0.0, m)))


def online_softmax_update(carry, block):
    """One step of the streaming-softmax accumulation used by blockwise and
    ring attention: merge a new (m_blk, l_blk, o_blk) partial into the
    running (o, l, m).  Shapes: m,l (..., Tq); o (..., Tq, D)."""
    o, l, m = carry
    m_blk, l_blk, o_blk = block
    m_new = jnp.maximum(m, m_blk)
    alpha = _safe_exp(m, m_new)
    beta = _safe_exp(m_blk, m_new)
    o = o * alpha[..., None] + o_blk * beta[..., None]
    l = l * alpha + l_blk * beta
    return o, l, m_new


def segment_mask(seg_q, seg_k):
    """(B, 1, Tq, Tk) boolean packed-document isolation mask from
    (B, Tq)/(B, Tk) segment ids — broadcasts over the head dim; the one
    definition of the layout every attention path shares."""
    return seg_q[:, None, :, None] == seg_k[:, None, None, :]


def _block_scores(q, k, v, mask, scale, acc=None):
    """Partial attention of q against one k/v block.
    q: (..., Tq, D); k: (..., Tk, D); v: (..., Tk, Dv); mask: broadcastable
    (..., Tq, Tk) or None.  ``acc`` (a dtype): scores, softmax and sums in
    it whatever the operands' (the weights meet v in v's dtype); None: the
    operands' own.  Returns (m_blk (...,Tq), l_blk (...,Tq), o_blk
    (...,Tq,Dv))."""
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=acc) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    p = _safe_exp(s, m_blk[..., None])
    l_blk = jnp.sum(p, axis=-1)
    o_blk = jnp.einsum("...qk,...kd->...qd",
                       p if acc is None else p.astype(v.dtype), v,
                       preferred_element_type=acc)
    return m_blk, l_blk, o_blk


def _finalize(o, l):
    return o / jnp.where(l == 0.0, 1.0, l)[..., None]


def dot_product_attention(q, k, v, *, causal: bool = False, mask=None,
                          scale: Optional[float] = None):
    """Plain attention, one XLA fusion. q,k,v: (..., T, D)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if causal:
        tq, tk = q.shape[-2], k.shape[-2]
        cmask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        mask = cmask if mask is None else jnp.logical_and(mask, cmask)
    m, l, o = _block_scores(q, k, v, mask, scale)
    return _finalize(o, l)


def blockwise_attention(q, k, v, *, block_size: int = 512,
                        causal: bool = False,
                        scale: Optional[float] = None):
    """Memory-efficient streaming attention: the (Tq, Tk) score matrix is
    never materialized — k/v are consumed in blocks with an online softmax
    (the single-chip half of ring attention; HBM-bound regime).
    q,k,v: (B, H, T, D)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    tk = k.shape[-2]
    block_size = min(block_size, tk)
    rem = tk % block_size
    padded = rem != 0
    if padded:  # pad the tail block; pad keys are masked out by position
        pad = block_size - rem
        widths = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    n_blocks = k.shape[-2] // block_size
    k_blocks = k.reshape(k.shape[:-2] + (n_blocks, block_size, k.shape[-1]))
    v_blocks = v.reshape(v.shape[:-2] + (n_blocks, block_size, v.shape[-1]))
    k_blocks = jnp.moveaxis(k_blocks, -3, 0)  # (n, B, H, bs, D)
    v_blocks = jnp.moveaxis(v_blocks, -3, 0)
    tq = q.shape[-2]
    q_pos = jnp.arange(tq) + (tk - tq)  # align ends when Tq != Tk

    def step(carry, inp):
        blk_idx, kb, vb = inp
        mask = None
        if causal or padded:
            k_pos = blk_idx * block_size + jnp.arange(block_size)
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal \
                else jnp.ones((tq, block_size), bool)
            if padded:
                mask = jnp.logical_and(mask, (k_pos < tk)[None, :])
        blk = _block_scores(q, kb, vb, mask, scale)
        return online_softmax_update(carry, blk), None

    o0 = jnp.zeros(q.shape, q.dtype)
    l0 = jnp.zeros(q.shape[:-1], q.dtype)
    m0 = jnp.full(q.shape[:-1], NEG_INF, q.dtype)
    (o, l, _), _ = lax.scan(
        step, (o0, l0, m0), (jnp.arange(n_blocks), k_blocks, v_blocks))
    return _finalize(o, l)


class MultiHeadAttention(Module):
    """Multi-head attention over (B, T, F) (post-reference capability; the
    TPU-idiomatic replacement for long-sequence modeling that the
    reference's Recurrent stack cannot scale to).

    Input: a tensor (self-attention) or a table/tuple (query, key, value).
    """

    def __init__(self, hidden_size: int, n_head: int,
                 head_dim: Optional[int] = None, causal: bool = False,
                 with_bias: bool = True, block_size: Optional[int] = None,
                 attention_impl: str = "auto"):
        super().__init__()
        assert head_dim is not None or hidden_size % n_head == 0
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = head_dim or hidden_size // n_head
        self.causal = causal
        self.with_bias = with_bias
        self.block_size = block_size  # None -> plain fused attention
        # "xla": always the fused XLA attention (required under GSPMD
        # sharding rules — pallas_call only partitions inside shard_map);
        # "flash": always the Pallas kernel; "auto": crossover dispatch —
        # flash on TPU past FLASH_AUTO_MIN_T, XLA otherwise
        if attention_impl not in ("auto", "flash", "xla"):
            raise ValueError(f"attention_impl must be 'auto', 'flash' or "
                             f"'xla', got {attention_impl!r}")
        self.attention_impl = attention_impl

    def init(self, rng):
        ks = jax.random.split(rng, 4)
        inner = self.n_head * self.head_dim
        std = 1.0 / math.sqrt(self.hidden_size)
        p = {name: jax.random.uniform(k, shape, jnp.float32, -std, std)
             for name, k, shape in (
                 ("wq", ks[0], (self.hidden_size, inner)),
                 ("wk", ks[1], (self.hidden_size, inner)),
                 ("wv", ks[2], (self.hidden_size, inner)),
                 ("wo", ks[3], (inner, self.hidden_size)))}
        if self.with_bias:
            for name in ("bq", "bk", "bv", "bo"):
                p[name] = jnp.zeros((self.hidden_size,)
                                    if name == "bo" else (inner,))
        return p

    def resolve_use_flash(self, seq_len: int) -> bool:
        """ONE dispatch rule for every call path (module forward,
        TransformerLM block, generation prefill): explicit "flash" always;
        "xla" never; "auto" on a TPU from ``FLASH_AUTO_MIN_T`` positions
        up (``ops.flash_attention.use_flash_auto``) — unless a block_size
        was set, which pins the blockwise-XLA core."""
        if self.attention_impl == "flash":
            return True
        if self.attention_impl == "auto" and not self.block_size:
            from bigdl_tpu.ops.flash_attention import use_flash_auto
            return use_flash_auto(seq_len)
        return False

    def _split_heads(self, x):  # (B, T, H*D) -> (B, H, T, D)
        b, t, _ = x.shape
        return x.reshape(b, t, self.n_head, self.head_dim).transpose(0, 2, 1, 3)

    def _merge_heads(self, x):  # (B, H, T, D) -> (B, T, H*D)
        b, h, t, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def project_qkv(self, params, q_in, k_in, v_in):
        # qmatmul is the QTensor-aware seam: plain arrays fall straight
        # through to @, int8-compute drafter weights hit the MXU as int8
        from bigdl_tpu.quant.kernels import qmatmul
        q_in = match_compute_dtype(jnp.asarray(q_in), params["wq"])
        k_in = match_compute_dtype(jnp.asarray(k_in), params["wk"])
        v_in = match_compute_dtype(jnp.asarray(v_in), params["wv"])
        q = qmatmul(q_in, params["wq"])
        k = qmatmul(k_in, params["wk"])
        v = qmatmul(v_in, params["wv"])
        if self.with_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        return (self._split_heads(q), self._split_heads(k),
                self._split_heads(v))

    def project_out(self, params, o):
        from bigdl_tpu.quant.kernels import qmatmul
        y = qmatmul(self._merge_heads(o), params["wo"])
        if self.with_bias:
            y = y + params["bo"]
        return y

    def attend(self, q, k, v, *, segment_ids=None, allow_blockwise=True):
        """The ONE attention-core dispatch shared by the module forward
        and TransformerLM blocks: flash (per resolve_use_flash) ->
        blockwise (pinned block_size, module path only) -> plain XLA.
        ``segment_ids`` (B, T): packed-document isolation, self-attention
        only — masked inside the flash tiles or via an explicit mask on
        the plain path."""
        if segment_ids is not None and q.shape[-2] != k.shape[-2]:
            # mirror ops.flash_attention's guard so the XLA path fails
            # with the same clear message instead of a deep broadcast
            # error (and never silently masks k by q's document ids)
            raise ValueError("segment_ids requires self-attention "
                             "(Tq == Tk)")
        if self.resolve_use_flash(q.shape[-2]):
            from bigdl_tpu.ops import flash_attention
            return flash_attention(q, k, v, causal=self.causal,
                                   segment_ids=segment_ids,
                                   block_q=self.block_size,
                                   block_k=self.block_size)
        if self.block_size and allow_blockwise:
            if segment_ids is not None:
                raise ValueError(
                    "segment_ids is not supported with a pinned "
                    "block_size (blockwise-XLA core); use "
                    "attention_impl='flash', or unset block_size for "
                    "the plain XLA core")
            return blockwise_attention(q, k, v, block_size=self.block_size,
                                       causal=self.causal)
        mask = (None if segment_ids is None
                else segment_mask(segment_ids, segment_ids))
        return dot_product_attention(q, k, v, causal=self.causal, mask=mask)

    def f(self, params, x, *, segment_ids=None, **kw):
        """``segment_ids`` (B, T): packed-document isolation for the
        self-attention case — masked inside the flash tiles or via an
        explicit mask on the XLA paths (the same contract as
        ``ops.flash_attention`` and ``TransformerLM.doc_start_id``)."""
        from bigdl_tpu.utils.table import Table
        if isinstance(x, Table):
            q_in, k_in, v_in = x.to_seq()[:3]
        elif isinstance(x, (tuple, list)):
            q_in, k_in, v_in = x[0], x[1], x[2]
        else:
            q_in = k_in = v_in = x
        q, k, v = self.project_qkv(params, q_in, k_in, v_in)
        o = self.attend(q, k, v, segment_ids=segment_ids)
        return self.project_out(params, o)
