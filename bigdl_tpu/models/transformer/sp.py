"""Sequence-parallel TransformerLM: the whole forward under ``shard_map``
with the sequence dim sharded over a mesh axis and every attention block
running ring attention (neighbor ppermute over ICI, online-softmax merge —
``bigdl_tpu.parallel.sequence``).  This is the long-context composition the
survey's §5.7 gap-fill calls for, applied to the flagship LM: activations
never materialize the full sequence on one device, so context length
scales with the mesh instead of with HBM.

Everything except attention is token-local (LayerNorm, MLP, embedding,
head), so the only communication is the ring itself — one neighbor
exchange per hop, no all-gathers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.parallel.mesh import DATA_AXIS, SEQUENCE_AXIS
from bigdl_tpu.parallel.sequence import (ring_attention_local,
                                         ulysses_attention_local)


def ring_lm_apply(model: TransformerLM, params, ids, mesh: Mesh, *,
                  seq_axis: str = SEQUENCE_AXIS,
                  data_axis: Optional[str] = None,
                  impl: Optional[str] = None,
                  block_size: Optional[int] = None):
    """Sequence-parallel forward of ``model`` (a built ``TransformerLM``):
    ids (B, T) with T divisible by the ``seq_axis`` size; returns
    (B, T, vocab) log-probs sharded the same way the input was.

    On a pure sequence mesh leave ``data_axis`` at None (the
    ``ring_attention``/``ulysses`` convention); on a 2-D data x sequence
    mesh pass it so the batch dim stays data-sharded instead of every
    data row recomputing the full batch.

    The built model's configuration is authoritative: ``impl`` defaults
    from its ``attention_impl`` ("flash" -> the Pallas kernel inside every
    ring hop, the TPU long-context hot path), ``block_size`` from its
    block size, and ``model.remat`` wraps each block in ``jax.checkpoint``
    exactly as the single-device forward does.  Training-mode dropout is
    not supported under the ring (model.dropout must be 0).
    """
    mha = model._mha
    if impl is None:
        impl = "flash" if mha.attention_impl == "flash" else "blocks"
    if block_size is None:
        block_size = mha.block_size or 128

    def attn(q, k, v, seg=None):
        return ring_attention_local(q, k, v, seq_axis, causal=True,
                                    impl=impl, block_size=block_size,
                                    segment_ids=seg)

    return _sequence_parallel_apply(model, params, ids, mesh,
                                    seq_axis=seq_axis, data_axis=data_axis,
                                    attn_fn=attn)


def ulysses_lm_apply(model: TransformerLM, params, ids, mesh: Mesh, *,
                     seq_axis: str = SEQUENCE_AXIS,
                     data_axis: Optional[str] = None):
    """Ulysses variant of :func:`ring_lm_apply`: each attention block
    exchanges sequence shards for head shards (one ``all_to_all`` in, one
    out), runs full-sequence attention on ``n_head / axis_size`` heads,
    and every other sublayer stays token-local.  Prefer the ring when the
    sequence axis exceeds the head count; Ulysses moves less total data
    per block when heads divide evenly (two all-to-alls vs N-1 ppermute
    hops)."""
    axis_size = mesh.shape[seq_axis]
    if model.n_head % axis_size != 0:
        raise ValueError(
            f"Ulysses needs n_head ({model.n_head}) divisible by the "
            f"'{seq_axis}' axis size ({axis_size}); use ring_lm_apply "
            f"otherwise")

    def attn(q, k, v, seg=None):
        return ulysses_attention_local(q, k, v, seq_axis, causal=True,
                                       segment_ids_full=seg)

    # the (B, T) segment ids are layer-invariant: gather them ONCE per
    # step, outside the layer scan, instead of once per transformer layer
    # inside ulysses_attention_local (ADVICE r4)
    return _sequence_parallel_apply(
        model, params, ids, mesh, seq_axis=seq_axis, data_axis=data_axis,
        attn_fn=attn,
        seg_prepare=lambda s: lax.all_gather(s, seq_axis, axis=1,
                                             tiled=True))


def _sequence_parallel_apply(model, params, ids, mesh, *, seq_axis,
                             data_axis, attn_fn, seg_prepare=None):
    """Shared shard_map body: embedding + per-shard positions, scan over
    layer-stacked blocks with ``attn_fn`` as the (sequence-sharded)
    attention core, token-local LN/MLP/head.  Validation shared by both
    entry points lives here so the two cannot drift.  ``seg_prepare``
    transforms the (B, T_local) segment ids once per STEP, outside the
    layer scan, for cores that need a layer-invariant derived form
    (Ulysses pre-gathers the full (B, T) ids here rather than per
    layer)."""
    if model.dropout > 0.0:
        raise ValueError("sequence-parallel apply does not support "
                         "dropout — build the TransformerLM with dropout=0")
    if model.moe_experts:
        # routing/capacity would be shard-local and the aux loss has no
        # return path through this API; expert parallelism composes via
        # bigdl_tpu.parallel.expert.moe_apply instead
        raise ValueError("sequence-parallel apply does not support MoE "
                         "blocks yet — use the single-device forward or "
                         "parallel.expert.moe_apply")
    if ids.shape[-1] > model.max_len:
        # the per-shard dynamic_slice on the position table would CLAMP an
        # out-of-range offset and silently reuse trailing positions; fail
        # loudly like the single-device path does
        raise ValueError(
            f"sequence length {ids.shape[-1]} exceeds the model's "
            f"max_len {model.max_len}")
    mha = model._mha

    def local_fwd(params, ids_local):
        ids_i = jnp.asarray(ids_local)
        if jnp.issubdtype(ids_i.dtype, jnp.floating):
            ids_i = ids_i.astype(jnp.int32)
        ids_i = ids_i - 1
        t_local = ids_i.shape[-1]
        offset = lax.axis_index(seq_axis) * t_local
        h = params["embed"][ids_i]
        # GLOBAL positions for this shard: rope rotations and the learned
        # table both key on them (a key rotated at its own global
        # position stays correct as it travels the ring)
        positions = offset + jnp.arange(t_local)
        if model.pos_encoding == "learned":
            h = h + lax.dynamic_slice(params["pos"], (offset, 0),
                                      (t_local, params["pos"].shape[1]))

        seg_local = None
        if model.doc_start_id is not None:
            # GLOBAL segment ids from local shards: each shard's cumsum
            # plus the marker total of every shard before it on the axis
            # (one (N, B)-int all_gather — noise next to the k/v traffic)
            marker = (ids_i == model.doc_start_id - 1).astype(jnp.int32)
            local_cum = jnp.cumsum(marker, axis=-1)
            totals = lax.all_gather(local_cum[..., -1], seq_axis)  # (N, B)
            n_sh = totals.shape[0]
            my = lax.axis_index(seq_axis)
            prev = jnp.sum(
                jnp.where(jnp.arange(n_sh)[:, None] < my, totals, 0),
                axis=0)  # (B,)
            seg_local = local_cum + prev[:, None]
            if seg_prepare is not None:
                seg_local = seg_prepare(seg_local)

        def block(bp, h):
            a = model._layer_norm(bp["ln1"], h)
            q, k, v = mha.project_qkv(bp["attn"], a, a, a)
            q, k = model._rope(q, k, positions)
            o = attn_fn(q, k, v, seg_local)
            h = h + mha.project_out(bp["attn"], o)
            m = model._layer_norm(bp["ln2"], h)
            m, _ = model._mlp(bp, m)
            return h + m

        if model.remat:
            block = jax.checkpoint(block)
        h, _ = lax.scan(lambda carry, bp: (block(bp, carry), None),
                        h, params["blocks"])
        h = model._layer_norm(params["ln_f"], h)
        head = (params["embed"].T.astype(h.dtype) if model.tie_embeddings
                else params["head"].astype(h.dtype))
        logits = h @ head
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    io_spec = P(data_axis, seq_axis)
    fn = shard_map(local_fwd, mesh=mesh,
                   in_specs=(P(), io_spec),
                   out_specs=P(data_axis, seq_axis, None))
    return fn(params, ids)
