"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Capability-gap fill (SURVEY.md §5.7: the reference has no attention and no
sequence parallelism) designed TPU-first: the sequence dimension is a mesh
axis; k/v shards rotate around the ring with ``lax.ppermute`` (neighbor
exchanges ride ICI) while each hop's partial attention merges via the same
online-softmax update as blockwise attention, so the full (T, T) score
matrix never exists on any chip.  Ulysses instead trades two
``lax.all_to_all``s (sequence <-> heads) for full-sequence attention on a
head subset — cheaper at moderate T, ring wins at long T.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.nn.attention import (NEG_INF, _block_scores, _finalize,
                                    segment_mask,
                                    online_softmax_update)
from bigdl_tpu.parallel.mesh import SEQUENCE_AXIS


def ring_attention_local(q, k, v, axis_name: str, *, causal: bool = False,
                         scale: Optional[float] = None,
                         impl: str = "blocks", block_size: int = 128,
                         segment_ids=None):
    """Per-shard body of ring attention.  Must run inside ``shard_map``
    (or pmap) with ``axis_name`` bound; q, k, v: (B, H, T_local, D) — the
    local sequence shard.  Returns the local (B, H, T_local, D) output.

    Round r computes q against the k/v block that started on device
    (my_index - r) mod N, then passes its current block to the next device
    (a pure neighbor ppermute: ICI-friendly, no all-gather).

    ``impl="flash"`` computes each hop's partial attention with the
    Pallas flash kernel (bigdl_tpu.ops.flash_attention_with_lse) and
    merges hops by logsumexp weighting — the long-context hot path:
    VMEM-tiled inner attention composed with ICI ring exchanges.

    ``segment_ids`` (B, T_local): the LOCAL shard of the packed-document
    segment ids; the key-side shard rides the ring with k/v (one extra
    (B, T_local) int32 per hop — noise next to the k/v traffic), so
    isolation holds across shard boundaries exactly as on one chip."""
    if impl == "flash":
        return _ring_attention_local_flash(q, k, v, axis_name, causal=causal,
                                           scale=scale, block_size=block_size,
                                           segment_ids=segment_ids)
    if impl != "blocks":
        raise ValueError(f"impl must be 'blocks' or 'flash', got {impl!r}")
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    t_local = q.shape[-2]
    q_pos = my_idx * t_local + jnp.arange(t_local)  # global positions

    def _seg_mask(seg_kr):
        if seg_kr is None:
            return None
        return segment_mask(segment_ids, seg_kr)

    def hop(r, state, kvr):
        kr, vr, seg_kr = kvr
        o, l, m = state
        src = (my_idx - r) % n  # which shard this k/v block came from
        if not causal:
            return online_softmax_update(
                (o, l, m), _block_scores(q, kr, vr, _seg_mask(seg_kr), scale))

        # a block strictly in my future (src > my_idx) is fully masked:
        # cond skips its matmuls and merge at runtime entirely
        def masked_block(_):
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            smask = _seg_mask(seg_kr)
            if smask is not None:
                mask = jnp.logical_and(mask, smask)
            return online_softmax_update(
                (o, l, m), _block_scores(q, kr, vr, mask, scale))

        return lax.cond(src > my_idx, lambda _: (o, l, m), masked_block, None)

    # derive inits from q so the carry is marked varying over the
    # shard_map axis (plain jnp.zeros would be replicated, failing vma)
    o0 = q * 0.0
    l0 = q[..., 0] * 0.0
    m0 = q[..., 0] * 0.0 + NEG_INF
    o, l, _ = _ring_schedule(axis_name, n, (k, v, segment_ids),
                             (o0, l0, m0), hop)
    return _finalize(o, l)


def _ring_schedule(axis_name: str, n, kv, state0, hop):
    """The ring loop shared by both impls: rounds 0..n-1 of
    ``state = hop(r, state, kv_r)``, rotating the k/v pytree (k, v, and
    — when packed-document isolation is on — the key-side segment-id
    shard) to the next device after every round but the last (that
    rotation's carry would be discarded — pure wasted ICI traffic)."""
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(r, carry):
        state, kvr = carry
        state = hop(r, state, kvr)
        return state, jax.tree_util.tree_map(
            lambda a: lax.ppermute(a, axis_name, perm), kvr)

    state, kvr = lax.fori_loop(0, n - 1, step, (state0, kv))
    return hop(n - 1, state, kvr)


def _ring_attention_local_flash(q, k, v, axis_name: str, *,
                                causal: bool = False,
                                scale: Optional[float] = None,
                                block_size: int = 128,
                                segment_ids=None):
    """Ring attention with the Pallas flash kernel as the per-hop compute.

    Each hop yields a normalized partial (o_blk, lse_blk) over its key
    shard; disjoint-key partials merge exactly by logsumexp weighting.
    Causality by shard position: past shards attend unmasked, the
    diagonal shard uses the kernel's causal mask (Tq == Tk, aligned),
    future shards are skipped entirely (lax.cond saves their FLOPs).
    Accumulation runs in float32 regardless of input dtype (bf16 inputs
    feed the kernel's MXU tiles; the output is cast back)."""
    from bigdl_tpu.ops import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    bq = min(block_size, q.shape[-2])
    bk = min(block_size, k.shape[-2])

    def hop(r, state, kvr):
        kr, vr, seg_kr = kvr
        o, lse = state
        src = (my_idx - r) % n  # which shard this k/v block came from

        def run(is_causal):
            def f(_):
                ob, lb = flash_attention_with_lse(
                    q, kr, vr, causal=is_causal, scale=scale,
                    q_segment_ids=segment_ids, kv_segment_ids=seg_kr,
                    block_q=bq, block_k=bk)
                return ob.astype(jnp.float32), lb
            return f

        def skip(_):  # merge identity: o = 0, lse = -inf-ish
            # derive from q so the outputs carry q's varying-over-axis
            # marking and match the flash branches' types
            zero = (q[..., 0] * 0.0).astype(jnp.float32)
            return (q * 0.0).astype(jnp.float32), zero + NEG_INF

        if causal:
            o_blk, lse_blk = lax.cond(
                src > my_idx, skip,
                lambda _: lax.cond(src == my_idx, run(True), run(False),
                                   None), None)
        else:
            o_blk, lse_blk = run(False)(None)
        # exact merge of normalized partials over disjoint key sets
        lse_new = jnp.logaddexp(lse, lse_blk)
        w_old = jnp.exp(lse - lse_new)
        w_blk = jnp.exp(lse_blk - lse_new)
        o = o * w_old[..., None] + o_blk * w_blk[..., None]
        return o, lse_new

    o0 = (q * 0.0).astype(jnp.float32)
    lse0 = (q[..., 0] * 0.0).astype(jnp.float32) + NEG_INF
    o, _ = _ring_schedule(axis_name, n, (k, v, segment_ids),
                          (o0, lse0), hop)
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = SEQUENCE_AXIS,
                   batch_axis: Optional[str] = None, causal: bool = False,
                   impl: str = "blocks", block_size: int = 128,
                   segment_ids=None):
    """Global-view ring attention: q, k, v are (B, H, T, D) arrays (sharded
    or not); T is sharded over ``axis`` and the ring runs over that mesh
    axis.  On a 2-D mesh pass ``batch_axis`` so the batch dim stays
    data-sharded instead of being gathered.  ``impl="flash"`` uses the
    Pallas flash kernel for each hop's partial attention.
    ``segment_ids`` (B, T) int: packed-document isolation — sharded over
    the same axis; the key-side shard rides the ring."""
    spec = P(batch_axis, None, axis, None)
    if segment_ids is None:
        fn = shard_map(
            partial(ring_attention_local, axis_name=axis, causal=causal,
                    impl=impl, block_size=block_size),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    seg_spec = P(batch_axis, axis)
    fn = shard_map(
        lambda q, k, v, seg: ring_attention_local(
            q, k, v, axis_name=axis, causal=causal, impl=impl,
            block_size=block_size, segment_ids=seg),
        mesh=mesh, in_specs=(spec, spec, spec, seg_spec), out_specs=spec)
    return fn(q, k, v, segment_ids)


def ulysses_attention_local(q, k, v, axis_name: str, *,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            segment_ids=None, segment_ids_full=None):
    """Per-shard body of Ulysses (all-to-all) sequence parallelism.  Inside
    ``shard_map`` with q, k, v: (B, H, T_local, D), H divisible by the axis
    size: exchange sequence shards for head shards, run full-sequence
    attention on H/N heads, exchange back.  ``segment_ids`` (B, T_local):
    each device sees the FULL sequence after the all-to-all, so the full
    (B, T) ids are assembled with one small all_gather.  The ids are
    layer-invariant — a caller invoking this once per transformer layer
    (e.g. inside a layer scan) should gather once and pass the (B, T)
    result as ``segment_ids_full`` instead, skipping the per-layer
    gather."""
    n = lax.psum(1, axis_name)
    assert q.shape[1] % n == 0, \
        f"Ulysses needs n_head ({q.shape[1]}) divisible by axis size ({n})"

    def seq2head(x):  # (B, H, T_local, D) -> (B, H/N, T, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def head2seq(x):  # (B, H/N, T, D) -> (B, H, T_local, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        t = qh.shape[-2]
        mask = jnp.tril(jnp.ones((t, t), bool))
    if segment_ids_full is None and segment_ids is not None:
        segment_ids_full = lax.all_gather(segment_ids, axis_name, axis=1,
                                          tiled=True)  # (B, T)
    if segment_ids_full is not None:
        smask = segment_mask(segment_ids_full, segment_ids_full)
        mask = smask if mask is None else jnp.logical_and(mask, smask)
    m, l, o = _block_scores(qh, kh, vh, mask, scale)
    return head2seq(_finalize(o, l))


def ulysses_attention(q, k, v, mesh: Mesh, *, axis: str = SEQUENCE_AXIS,
                      batch_axis: Optional[str] = None,
                      causal: bool = False, segment_ids=None):
    """Global-view Ulysses attention (all-to-all sequence parallelism)."""
    spec = P(batch_axis, None, axis, None)
    if segment_ids is None:
        fn = shard_map(
            partial(ulysses_attention_local, axis_name=axis, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    seg_spec = P(batch_axis, axis)
    fn = shard_map(
        lambda q, k, v, seg: ulysses_attention_local(
            q, k, v, axis_name=axis, causal=causal, segment_ids=seg),
        mesh=mesh, in_specs=(spec, spec, spec, seg_spec), out_specs=spec)
    return fn(q, k, v, segment_ids)


def sequence_parallel_self_attention(mha, params, x, mesh: Mesh, *,
                                     axis: str = SEQUENCE_AXIS,
                                     batch_axis: Optional[str] = None,
                                     kind: str = "ring"):
    """Run a ``MultiHeadAttention`` module with its sequence dimension
    sharded over ``axis``: projections are position-local (stay sharded);
    the attention core runs as ring or Ulysses.  On a 2-D mesh pass
    ``batch_axis`` so the batch dim stays data-sharded."""
    if kind not in ("ring", "ulysses"):
        raise ValueError(f"kind must be 'ring' or 'ulysses', got {kind!r}")
    q, k, v = mha.project_qkv(params, x, x, x)
    attn = ring_attention if kind == "ring" else ulysses_attention
    o = attn(q, k, v, mesh, axis=axis, batch_axis=batch_axis,
             causal=mha.causal)
    return mha.project_out(params, o)
