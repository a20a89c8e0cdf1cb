"""Autoregressive decoding for ``TransformerLM`` (post-reference
capability: an LM family is not complete without sampling).

TPU-first decode: the whole generation loop is ONE jitted ``lax.scan``
over a static-shape KV cache — no per-token retracing, no dynamic
shapes.  Each step writes the new position's k/v into the cache with
``dynamic_update_slice`` and attends over the full cache under a
position mask, so step cost is O(T) and the (T, T) matrix never exists.
Prefill runs the prompt in one batched pass (the same block math as
``TransformerLM.f``) and records every position's k/v.

Greedy (temperature=0) decoding is oracle-tested against the naive
full-recompute argmax over ``model.apply``.  MoE note: decode always
uses DENSE per-token routing (capacity-factor dropping is a batch-level
training construct; under it a sequence's continuation would depend on
which unrelated prompts share the dispatch window).  Exact equality with
teacher-forced recompute therefore holds for
``moe_capacity_factor=None`` models; capacity-trained models may diverge
from a teacher-forced pass exactly where the full window would have
dropped tokens.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models.transformer import TransformerLM, window_mask
from bigdl_tpu.serving.kvcache.blocks import (SCRATCH_BLOCK, head_columns,
                                              head_lanes, list_chunk,
                                              read_chain, read_rows,
                                              split_live, table_list,
                                              write_rows)


def _head_logits(model, params, h):
    """LM-head matmul shared by every decode/prefill/verify path
    (``TransformerLM._head``) -- QTensor-aware so an int8-compute drafter's
    untied head runs on the int8 MXU path (tied heads ride the f32 embedding,
    which the quant policy never touches)."""
    return model._head(params, h)


def _finish_block(model, spec, bp, h, o, gate, token_mask=None):
    """After attention: gate, output projection and residual, then the
    feed-forward half.  The legacy switch MoE routes DENSELY here: its
    capacity window is a batch-level training construct -- under it a
    sequence's tokens would drop depending on which unrelated prompts
    share the dispatch, coupling batch rows.  -> (h, counts), ``counts``
    the routed expert layer's integers (zeros on a dense layer)."""
    return _ffn(model, spec, bp, h + model.layer_attn_out(bp, o, gate),
                token_mask)


def _ffn(model, spec, bp, h, token_mask=None):
    """A block's second half on the stream ``h`` its mixer has been added
    to: -> (h, counts)."""
    m, _, counts = model.layer_ffn(spec, bp, h, dense_routing=True,
                                   token_mask=token_mask)
    return h + m, counts


def _windows(model):
    """The distinct windows of the model's layers (``None``: full; a
    recurrent layer has none)."""
    return {s.window for _, period in model.plan for s in period}


def _attn_scope(model, spec):
    """The named scope of a layer's attention in the device trace."""
    if spec.window:
        return "attn/sliding"
    rotated = spec.rope is not None or model.pos_encoding != "none"
    return "attn/full" if rotated else "attn/nope"


def _kinds(model):
    """The kinds of cache a model's layers keep, each indexed by its own
    layers alone: a recurrent layer's state row (``"kda"``), a latent layer's
    arena of rows (``"mla"``), and a CLASS of softmax layers' K/V arenas (its
    index among ``model.cache_classes``)."""
    return ("kda", "mla") + tuple(range(len(model.cache_classes)))


def _kind(model, spec):
    """The kind of cache ``spec``'s layer keeps (:func:`_kinds`)."""
    return (model.cache_class(spec) if spec.mixer == "attention"
            else spec.mixer)


def _kind_indices(model, period):
    """For each layer of a period: (its kind, how many of its kind the period
    holds, which of them it is).  A layer's caches are indexed by KIND, each
    counted over the layers of its own kind alone."""
    kinds = [_kind(model, s) for s in period]
    return [(k, kinds.count(k), kinds[:i].count(k))
            for i, k in enumerate(kinds)]


def _class_arenas(model, kv, c):
    """Class ``c``'s arenas of a step's flat ``kv`` (``(k, v)`` a class side
    by side; one class: all of them, an int8 pool's scales too)."""
    n = len(kv) // max(len(model.cache_classes), 1)
    return tuple(kv[c * n:(c + 1) * n])


def _with_class(kv, c, new):
    """``kv`` with class ``c``'s arenas replaced by ``new``."""
    n = len(new)
    return tuple(kv[:c * n]) + tuple(new) + tuple(kv[(c + 1) * n:])


def _split_arenas(model, arenas):
    """A step's arenas -> (the paged pool's ``(k, v)``, ``(k, v, ks, vs)``
    or, latent, ``(rows,)``, the recurrent layers' ``(state, tail)`` or
    ``()``): the state arenas ride last (``serving.kvcache.state``)."""
    n = 2 if model.state_layers else 0
    return tuple(arenas[:len(arenas) - n]), tuple(arenas[len(arenas) - n:])


def _scan_prefill(model, params, h, layer_fn):
    """A prefill's layer loop over the plan: ``layer_fn(spec, h, bp,
    index) -> (h, (a, b), counts)``, ``index`` the layer's place among the
    layers of its kind and ``(a, b)`` what it caches: an attention layer's
    k and v, a recurrent layer's state and convolution tail, a latent
    layer's ``(rows,)``.  -> (h, (k, v) or (rows,), (state, tail), counts):
    k/v (L_kv, B, H_kv, T, D) stacked by attention layer, rows (L_mla, B, T,
    lanes) by latent layer, state/tail stacked by recurrent layer (``()``
    for a model with none), the routed expert layers' integers summed."""
    kinds = _kinds(model)
    kept = {m: [] for m in kinds}
    base = {m: 0 for m in kinds}
    counts = jnp.zeros((model.n_counts,), jnp.int32)
    for (repeat, period), stacks in zip(model.plan,
                                        model.group_params(params)):
        where = _kind_indices(model, period)

        def body(carry, x, period=period, base=dict(base), where=where):
            h, counts = carry
            bps, r = x
            made = {m: [] for m in kinds}
            for spec, bp, (kind, n, j) in zip(period, bps, where):
                h, pair, c = layer_fn(spec, h, bp, base[kind] + r * n + j)
                made[kind].append(pair)
                counts = counts + c
            return (h, counts), tuple(
                tuple(jnp.stack(c) for c in zip(*made[m])) for m in kinds)

        (h, counts), out = lax.scan(body, (h, counts),
                                    (stacks, jnp.arange(repeat)))
        for m, pair in zip(kinds, out):
            if pair:        # (repeat, n of the kind, ..) -> by layer of the kind
                kept[m].append(tuple(x.reshape((-1,) + x.shape[2:])
                                     for x in pair))
        for kind, _, _ in where:
            base[kind] += repeat

    def whole(parts):
        if len(parts) <= 1:
            return parts[0] if parts else ()
        return tuple(jnp.concatenate(x) for x in zip(*parts))

    return (h, sum((whole(kept[c]) for c in kinds[2:]), ())
            + whole(kept["mla"]), whole(kept["kda"]), counts)


def _prefill_result(model, logits, kv, state, counts, h_last=None):
    """What a prefill hands back: (logits, k, v), or (logits, rows) of a
    model whose pool is latent; with routed expert layers in the model their
    integers ride out behind those, with recurrent layers each one's state
    and convolution tail at the prompt's true end behind those, and last,
    where the prediction module drafts (``h_last``), the main model's hidden
    state at the prompt's true end."""
    out = (logits.astype(jnp.float32),) + tuple(kv)
    if model.moe_layers:
        out += (counts,)
    out += tuple(state)
    return out if h_last is None else out + (h_last,)


def _mtp_rows(model, params, h, h_prev, ids0, positions, kv, last_index):
    """What the prediction module's block caches of a prefilled chunk, ONE
    MORE LATENT LAYER of rows behind the main layers': the row stored at
    position j is the pair's of (the main model's hidden state at j - 1, the
    token at j), rotated at j - 1 -- stored one position on, so that a row
    depends on the tokens up to its OWN position alone and a shared prefix's
    rows are every sharer's.  ``h`` (B, T, hidden) the chunk's hidden states
    before ``ln_f``, ``h_prev`` (B, hidden) the one before the chunk (zeros at
    a prompt's start: position 0 holds no pair, and no query sees it),
    ``positions`` (T,) the chunk's own, ``kv`` the main layers' ``(rows,)``.
    The block's rows need no attention: a row is its input's projection.
    -> (``(rows (L + 1, B, T, lanes),)``, the hidden state at ``last_index``
    (B, hidden): the next chunk's ``h_prev``, a slot's first pair's)."""
    hs = jnp.concatenate([h_prev[:, None].astype(h.dtype), h[:, :-1]], axis=1)
    z = model.mtp_embed(params, hs, ids0)
    with jax.named_scope("mtp/block"):
        row = model.mla_inputs(model.mtp, params["mtp"]["block"], z,
                               positions - 1)[1]
    h_last = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)[:, 0]
    return (jnp.concatenate([kv[0], row[None].astype(kv[0].dtype)]),), h_last


def _prefill_parts(model, params, ids0, last_index, *, mtp: bool = False):
    """Run a (possibly padded) prompt once; return (logits at
    ``last_index``, k, v) with k/v (L, B, H, T, D) — T the prompt width
    as given, NOT padded to any cache length (the caller pads for the
    offline scan, or slot-inserts for serving).  ``last_index`` may be
    traced: a bucket-padded serving prefill reads the logits at the TRUE
    prompt end while the padded tail rows stay causally masked (a padded
    key at position >= last_index+1 is never attended by the query at
    ``last_index``)."""
    b, t = ids0.shape
    h = params["embed"][ids0]
    if model.pos_encoding == "learned":
        h = h + params["pos"][:t]
    positions = jnp.arange(t)

    def layer_fn(spec, h, bp, index):
        if spec.mixer == "kda":
            # from a sequence's start; the padded tail touches neither the
            # state nor the convolution tail that are handed out
            y, state, tail = model.layer_kda(bp, h, length=last_index + 1)
            h, c = _ffn(model, spec, bp, h + y)
            return h, (state, tail), c
        if spec.mixer == "mla":
            q, row, gate = model.mla_inputs(spec, bp, h, positions)
            h, c = _finish_block(model, spec, bp, h,
                                 model.attend_latent(bp, q, row), gate)
            return h, (row,), c
        q, k, v, gate = model.layer_qkv(spec, bp, h, positions)
        # the model's configured attention core via the shared dispatch
        # (flash keeps the (T, T) matrix out of HBM for long prompts,
        # exactly as in TransformerLM._block -- including the "auto"
        # crossover rule)
        with jax.named_scope(_attn_scope(model, spec)):
            o = model.attend_full(spec, q, k, v,
                                  sink=model.layer_sink(spec, bp))
        h, c = _finish_block(model, spec, bp, h, o, gate)
        return h, (k, v), c

    h, kv, state, counts = _scan_prefill(model, params, h, layer_fn)
    h_last = None
    if mtp:     # the prediction module drafts: its rows, one layer more
        kv, h_last = _mtp_rows(
            model, params, h, jnp.zeros((b, h.shape[-1]), h.dtype), ids0,
            positions, kv, last_index)
    h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
    h = model._norm(params["ln_f"], h)
    logits = _head_logits(model, params, h)[:, 0]
    return _prefill_result(model, logits, kv, state, counts, h_last)


def _prefill(model, params, ids0, cache_len):
    """Offline prefill: prompt logits + k/v padded to (L, B, H,
    cache_len, D), ready for the in-place decode scan.  Jitted per model
    by :func:`_offline_programs`."""
    from bigdl_tpu.quant import dequantize_entry
    params = dequantize_entry(params)  # int8 clones generate too
    t = ids0.shape[1]
    logits, k, v = _prefill_parts(model, params, ids0, t - 1)[:3]
    pad = ((0, 0), (0, 0), (0, 0), (0, cache_len - t), (0, 0))
    return logits, jnp.pad(k, pad), jnp.pad(v, pad)


def _decode_step_slots(model, params, token, pos, k_cache, v_cache):
    """One cached decode step over S independent *slots*: token (S,)
    0-based, pos (S,) per-slot index of the position being *written*
    (slots decode unrelated requests, so each carries its own position).
    Caches (L, S, H, cache_len, D).  Returns (next logits (S, V) f32,
    caches').  The serving engine jits this with the caches donated so
    the decode loop never copies HBM-resident state."""
    if model.layer_plan is not None:
        raise NotImplementedError(
            "the slot caches hold one uniform stack of layers; a model with "
            "a layer plan decodes through the paged engine "
            "(serving.LMServingEngine)")
    spec = model.plan[0][1][0]
    h = params["embed"][token][:, None, :]
    if model.pos_encoding == "learned":
        h = h + params["pos"][pos][:, None, :]
    # (S, 1, 1): broadcasts against (S, H, 1, half) inside apply_rope —
    # every slot's key/query rotates at that slot's own position
    positions = pos[:, None, None]
    cache_len = k_cache.shape[3]
    # per-slot mask over cache positions: slot s attends to <= pos[s]
    mask = (jnp.arange(cache_len)[None, :] <= pos[:, None])[:, None, None, :]
    # per-slot cache write: dynamic_update_slice needs scalar starts, so
    # vmap it over the slot axis ((H, C, D) cache rows, scalar position)
    upd = jax.vmap(lambda c, u, p: lax.dynamic_update_slice(c, u, (0, p, 0)))

    def body(carry, layer):
        h = carry
        bp, kc, vc = layer
        # q, k, v: (S, H, 1, D); keys rotate at THEIR position
        q, k, v, gate = model.layer_qkv(spec, bp, h, positions)
        kc = upd(kc, k, pos)
        vc = upd(vc, v, pos)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            kc.astype(jnp.float32))
        scores = scores / jnp.sqrt(jnp.float32(model.head_dim))
        scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, vc.astype(jnp.float32))
        h, _ = _finish_block(model, spec, bp, h, o.astype(h.dtype), gate)
        return h, (kc, vc)

    h, (k_cache, v_cache) = lax.scan(body, h,
                                     (params["blocks"], k_cache, v_cache))
    h = model._norm(params["ln_f"], h)
    logits = _head_logits(model, params, h)[:, 0]
    return logits.astype(jnp.float32), k_cache, v_cache


def _kv_quantize_rows(x):
    """Symmetric int8 rows for the quantized KV arenas: ``x`` (..., D)
    float -> (q int8 (..., D), scale f32 (...,)) with per-row absmax
    scales (one scale per (position, head) row — the granularity the
    paged gather can rescale for free)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def _list_masks(model, live, q_pos, block_len):
    """The live list's position masks by window: entry ``i`` holds its
    owner's positions ``where * B + b``, seen by that owner's query rows
    at ``q_pos`` (S, W) -> {window: (n, W, B) bool}.  A padded entry is
    owned by nobody (owner S) and seen by nobody."""
    _, owner, where = live
    s = q_pos.shape[0]
    k_pos = where[:, None] * block_len + jnp.arange(block_len)[None, :]
    q_pos = q_pos[jnp.minimum(owner, s - 1)]                  # (n, W)
    owned = (owner < s)[:, None, None]
    return {w: window_mask(q_pos, k_pos, w) & owned for w in _windows(model)}


def _pieces(x):
    """f32 ``x`` as three bfloat16 pieces on a new LEADING axis, summing
    back to ``x`` to f32 round-off (8 + 8 + 8 mantissa bits): a matmul
    of bfloat16 pool rows against the pieces has exact products and f32
    sums at one pass of the matrix unit."""
    out, rest = [], x
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        out.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return jnp.stack(out)


def _pool_dot(dot, x, rows, fold=None):
    """``dot(x, rows)`` of f32 ``x`` against pool ``rows`` with exact
    products and f32 sums.  f32 rows run at the highest precision;
    bfloat16 rows (and int8 ones, which bfloat16 holds exactly) meet
    ``x`` in three bfloat16 pieces, on a leading axis or where ``fold``
    puts them for ``dot`` (more columns); -> (out, pieces), the pieces
    still to be added up by the caller, where they were put."""
    if rows.dtype == jnp.float32:
        return dot(x, rows, precision=lax.Precision.HIGHEST), 1
    split = _pieces(x)
    return dot(split if fold is None else fold(split),
               rows.astype(jnp.bfloat16),
               preferred_element_type=jnp.float32), 3


def _attend_all_pairs(q, kg, vg, mask, scales):
    """Attention over a live list when a K/V head has ONE query vector a
    slot (plain decode, heads not grouped): a slot's rows against its
    query would be a matrix-vector product a head, which fills nothing
    of the matrix unit, so every slot's query meets EVERY listed
    position, a head at a time (``(S, D) x (D, P)``), and the mask keeps
    each slot its own positions: today's softmax over a ``(S, H, P)``
    score tensor, P the list's positions and not slots x the table.
    ``q`` (S, H, D) f32, ``kg``/``vg`` (P, H, D) pool rows, ``mask`` (S,
    P), ``scales`` None or the int8 rows' (P, H) pair.  -> the softmax's
    three parts over these positions: the maximum (S, H), the sum of
    ``exp(score - maximum)`` (S, H) and the rows weighted by it (S, H,
    D)."""
    scores, _ = _pool_dot(
        lambda x, rows, **kw: jnp.einsum("...shd,phd->...shp", x, rows, **kw),
        q, kg)
    scores = scores.reshape((-1,) + scores.shape[-3:]).sum(0)
    if scales is not None:
        scores = scores * scales[0].T[None]
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = mask[:, None, :]
    top = jnp.max(jnp.where(seen, scores, -1e30), axis=-1)
    e = jnp.where(seen, jnp.exp(scores - top[..., None]), 0.0)
    den = jnp.sum(e, axis=-1)
    if scales is not None:
        e = e * scales[1].T[None]
    o, _ = _pool_dot(
        lambda x, rows, **kw: jnp.einsum("...shp,phd->...shd", x, rows, **kw),
        e, vg)
    return top, den, o.reshape((-1,) + o.shape[-3:]).sum(0)


#: a slot's weights against its run of rows: contract the row axis of
#: (P, C) with that of (P, lanes), ragged by slot -> (S, C, lanes)
_BY_SLOT = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _attend_by_owner(q, k_rows, v_rows, owner, mask, scales, score_dim=None,
                     v_dim=None):
    """Attention over a live list when a K/V head has SEVERAL query
    vectors a slot (grouped heads, or the candidate rows of a verify
    step): two grouped matmuls (``lax.ragged_dot``: the groups are the
    owners' runs of rows, the list being sorted by owner) of the rows as
    they lie.  A slot's queries are columns that are zero outside their
    K/V head's lanes, so ``rows @ columns`` is every position's score
    under each of its owner's queries; the softmax is the slot's own
    over every position its blocks hold (its maximum and its sum taken
    block by block, then by owner); the weighted V rows come back as
    ``(columns, lanes)`` a slot, of which each column keeps its head.
    ``q`` (S, H_kv, G, W, D) f32, ``k_rows``/``v_rows`` (P, lanes),
    ``mask`` (n, W, B), ``scales`` None or the int8 rows' (P, H_kv) pair;
    ``score_dim``: the width a score is scaled by where it is not D (an
    absorbed latent query is longer than the head it stands for); ``v_dim``:
    a value head's lanes where they are not the key's.
    -> the softmax's three parts over these blocks: the maximum (S, H_kv,
    G, W), the sum of ``exp(score - maximum)`` and the rows weighted by it
    (.., D)."""
    s_, n_kv, g, w_, d = q.shape
    n, B, c = owner.shape[0], mask.shape[2], g * w_
    q = q.reshape(s_, n_kv, c, d)
    own = jnp.minimum(owner, s_ - 1)
    mine = owner[None, :] == jnp.arange(s_)[:, None]          # (S, n)
    sizes = B * jnp.sum(mine, axis=1, dtype=jnp.int32)        # rows a slot
    columns = lambda p: jnp.concatenate(list(p), axis=-1)    # noqa: E731
    scores, pieces = _pool_dot(
        lambda x, rows, **kw: lax.ragged_dot(rows, x, sizes, **kw),
        head_columns(q, k_rows.shape[1]), k_rows, columns)
    # the pieces added up BEFORE the rows are split into blocks: summed as
    # (n, B, pieces, H_kv, C) the TPU compiler lays the scores out with B
    # in the lanes and the step reads a third longer (PERF.md, PR 29)
    scores = scores.reshape(n * B, pieces, n_kv * c).sum(1).reshape(
        n, B, n_kv, c)
    if scales is not None:          # an int8 row's scale, per (position, head)
        scores = scores * scales[0].reshape(n, B, n_kv, 1)
    scores = scores / jnp.sqrt(jnp.float32(score_dim or d))
    seen = jnp.broadcast_to(mask.transpose(0, 2, 1)[:, :, None, None, :],
                            (n, B, n_kv, g, w_)).reshape(scores.shape)
    scores = jnp.where(seen, scores, -1e30)
    top = jnp.max(jnp.where(mine[..., None, None],
                            jnp.max(scores, axis=1)[None], -1e30), axis=1)
    e = jnp.where(seen, jnp.exp(scores - top[own][:, None]), 0.0)
    den = jnp.einsum("sn,nkc->skc", mine.astype(jnp.float32),
                     jnp.sum(e, axis=1), precision=lax.Precision.HIGHEST)
    if scales is not None:
        e = e * scales[1].reshape(n, B, n_kv, 1)
    o, pieces = _pool_dot(
        lambda x, rows, **kw: lax.ragged_dot_general(x, rows, sizes, _BY_SLOT,
                                                     **kw),
        e.reshape(n * B, n_kv * c), v_rows, columns)  # (S, pieces * H_kv * C, lanes)
    # each column's own head first (an eighth, a 25th of the lanes), then
    # the pieces added up
    o = head_lanes(o.reshape(s_ * pieces, n_kv * c, -1), n_kv, v_dim or d)
    o = jnp.sum(o.reshape((s_, pieces) + o.shape[1:]), axis=1)
    part = (s_, n_kv, g, w_)
    return (top.reshape(part), den.reshape(part),
            o.reshape(part + (v_dim or d,)))


def _paged_attention(q, k, v, arenas, layer, blk, off, live, mask,
                     score_dim=None, sink=None):
    """One layer's cached attention over PAGED arenas, shared by the
    decode, verify and tree-verify steps: write the W new rows of each
    slot (``k``/``v`` (S, H_kv, W, D), row j at ``(blk, off)[s, j]``)
    into ``arenas[..][layer]``, then attend ``q`` (S, H, W, D) over the
    blocks of the live list ``live`` (3, n) -- which block, whose, where
    in the chain, sorted by owner (``serving.kvcache.blocks``) -- under
    ``mask`` (n, W, B): the owner's query rows against the block's
    positions; query head i reads K/V head ``i // (H / H_kv)``.  The list
    is walked a chunk at a time (``list_chunk``) by a loop that stops
    after the last chunk holding a listed block, so ONLY listed blocks
    (to within a chunk) are gathered, block index major, and nothing of
    them is copied to f32 or re-laid out.  How a chunk's rows meet the
    queries follows from how many query vectors a slot has for one K/V
    head: one (:func:`_attend_all_pairs`) or several
    (:func:`_attend_by_owner`); either hands back the three parts of a
    softmax over the chunk, and the chunks' parts are one softmax once
    each is rescaled to the larger maximum.  ``arenas`` is ``(k, v)``
    or, for an int8 pool, ``(k, v, k_scale, v_scale)``: rows are
    quantized per (position, head) on the way in and rescaled in
    flight.  Products are exact and scores, softmax and sums f32
    (:func:`_pool_dot`), so what a whole table gave differs by the order
    of the f32 sums only.
    A LATENT pool (``v=None``, ``arenas`` its one ``(rows,)``): ``k`` (S, 1,
    W, lanes) are the new rows, ``q`` the ABSORBED queries of every head (S,
    H, W, lanes) -- H query vectors a slot against the one row a position,
    the grouped case with one K/V head -- scaled by ``score_dim``, and the
    values are the SAME gathered rows (no second gather): ``o``'s leading
    ``kv_rank`` lanes are the weighted latents.
    ``v`` may be narrower than ``k`` (``D_v``: the arenas' rows differ in
    width); ``sink`` (H,) float32, a layer's learned sink: one more column
    of every row's softmax, with a probability and no value, added once the
    list is walked.
    Returns (o (S, H, W, D_v) f32, arenas')."""
    n_kv, d = k.shape[1], k.shape[3]
    dv = d if v is None else v.shape[3]
    B = arenas[0].shape[2]
    latent = v is None
    k = k.transpose(0, 2, 1, 3)                                # (S, W, H, D)
    quant = len(arenas) == 4
    if latent:
        ka = va = write_rows(arenas[0], layer, blk, off, k)
        arenas = (ka,)
    else:
        v = v.transpose(0, 2, 1, 3)
        if quant:
            ka, va, ksa, vsa = arenas
            k, ksr = _kv_quantize_rows(k)
            v, vsr = _kv_quantize_rows(v)
            ksa = write_rows(ksa, layer, blk, off, ksr)
            vsa = write_rows(vsa, layer, blk, off, vsr)
        else:
            ka, va = arenas
        ka = write_rows(ka, layer, blk, off, k)
        va = write_rows(va, layer, blk, off, v)
        arenas = (ka, va, ksa, vsa) if quant else (ka, va)
    s_, h_, w_ = q.shape[:3]
    g = h_ // n_kv
    q = q.astype(jnp.float32).reshape(s_, n_kv, g, w_, d)
    # the list a chunk at a time: as many chunks as hold a listed block
    grouped = latent or g * w_ > 1
    chunk = min(list_chunk(s_, grouped, latent), live.shape[1])
    pad = -live.shape[1] % chunk
    live = jnp.pad(live, ((0, 0), (0, pad)), constant_values=s_)
    mask = jnp.pad(mask, ((0, pad), (0, 0), (0, 0)))
    chunks = (jnp.sum(live[1] < s_) + chunk - 1) // chunk

    def attend(i, parts):
        ids, owner, _ = lax.dynamic_slice_in_dim(live, i * chunk, chunk, axis=1)
        seen = lax.dynamic_slice_in_dim(mask, i * chunk, chunk, axis=0)
        # (P, H_kv): an int8 row's scale a head
        scales = (tuple(read_chain(a, layer, ids, (B, n_kv))
                        for a in (ksa, vsa)) if quant else None)
        if not grouped:             # one query vector a slot and K/V head
            block = (B, n_kv, d)
            mine = jnp.repeat(owner, B)[None, :] == jnp.arange(s_)[:, None]
            new = _attend_all_pairs(
                q[:, :, 0, 0], read_chain(ka, layer, ids, block),
                read_chain(va, layer, ids, (B, n_kv, dv)),
                mine & seen[:, 0].reshape(-1)[None, :], scales)
            new = tuple(x.reshape(x.shape[:2] + (1, 1) + x.shape[2:])
                        for x in new)
        else:
            k_rows = read_rows(ka, layer, ids)
            new = _attend_by_owner(
                q, k_rows, k_rows if latent else read_rows(va, layer, ids),
                owner, seen, scales, score_dim, dv)
        # one softmax over every chunk: each part rescaled to the larger
        # maximum (a slot with nothing in a chunk adds exp(-1e30 - ..) = 0)
        top = jnp.maximum(parts[0], new[0])
        old, add = jnp.exp(parts[0] - top), jnp.exp(new[0] - top)
        return (top, parts[1] * old + new[1] * add,
                parts[2] * old[..., None] + new[2] * add[..., None])

    part = (s_, n_kv, g, w_)
    top, den, o = lax.fori_loop(
        0, chunks, attend,
        (jnp.full(part, -1e30, jnp.float32), jnp.zeros(part, jnp.float32),
         jnp.zeros(part + (dv,), jnp.float32)))
    if sink is not None:
        # the sink's column: rescale to the larger maximum, add its mass
        with jax.named_scope("attn/sink"):
            sk = sink.astype(jnp.float32).reshape(1, n_kv, g, 1)
            new = jnp.maximum(top, sk)
            old = jnp.exp(top - new)
            den, o = den * old + jnp.exp(sk - new), o * old[..., None]
    # (a slot that owns nothing, an idle one, sums to 0 over 0: unread)
    o = o / jnp.maximum(den, 1e-30)[..., None]
    return o.reshape(s_, h_, w_, dv), arenas


def _scan_layers(model, params, h, arenas, layer_fn):
    """The paged steps' layer loop over the model's plan: the arenas ride
    the CARRY whole and each layer indexes them itself by its place among
    the layers of its kind (``layer_fn(spec, h, bp, index, arenas) -> (h,
    arenas, counts)``; :func:`_kind_indices`: a model of attention layers
    alone indexes by absolute layer), so the compiled loop updates the
    donated buffers in
    place; threaded as ``xs`` and stacked ``ys`` they were re-laid out
    and copied every layer (PERF.md, PR 25).  A group of the plan is one
    scan over its stacked periods, the body running the period's layers
    in turn.  -> (h, arenas, counts): ``counts`` the routed expert
    layers' integers summed over the layers."""
    counts = jnp.zeros((model.n_counts,), jnp.int32)
    base = {m: 0 for m in _kinds(model)}
    arenas = tuple(arenas)
    for (repeat, period), stacks in zip(model.plan,
                                        model.group_params(params)):
        where = _kind_indices(model, period)

        def body(carry, x, period=period, base=dict(base), where=where):
            h, arenas, counts = carry
            bps, r = x
            for spec, bp, (kind, n, j) in zip(period, bps, where):
                h, arenas, c = layer_fn(spec, h, bp,
                                        base[kind] + r * n + j, arenas)
                counts = counts + c
            return (h, arenas, counts), None

        (h, arenas, counts), _ = lax.scan(
            body, (h, arenas, counts), (stacks, jnp.arange(repeat)))
        for kind, _, _ in where:
            base[kind] += repeat
    return h, arenas, counts


def _arenas(k_arena, v_arena, k_scale, v_scale):
    if v_arena is None:         # a latent pool's one arena
        return (k_arena,)
    return ((k_arena, v_arena) if k_scale is None
            else (k_arena, v_arena, k_scale, v_scale))


#: positions of a cached prefix that a latent layer's suffix prefill expands
#: and attends at a time (its float32 scores are H x suffix x this many)
LATENT_PREFIX_STEP = 2048


def _latent_prefix_parts(model, bp, q, arena, layer, blocks, prefix_len):
    """A latent layer's suffix queries ``q`` (1, H, Ts, nope + rope) against
    the CACHED PREFIX, read from the latent ``arena`` through the padded
    chain ``blocks`` and EXPANDED a step of :data:`LATENT_PREFIX_STEP`
    positions at a time, as far as ``prefix_len`` reaches (a loop of that
    many steps: a bucket's padding is not walked).  Every suffix query sees
    every prefix position.  -> the softmax's parts over the prefix, in the
    order :func:`~bigdl_tpu.nn.attention.online_softmax_update` carries them:
    (o (1, H, Ts, v), the sum, the maximum)."""
    from bigdl_tpu.nn.attention import NEG_INF, online_softmax_update
    B = arena.shape[2]
    step = min(max(LATENT_PREFIX_STEP // B, 1), blocks.shape[0])
    blocks = jnp.pad(blocks, (0, -blocks.shape[0] % step),
                     constant_values=SCRATCH_BLOCK)
    part = q.shape[:-1]

    def attend(i, carry):
        ids = lax.dynamic_slice_in_dim(blocks, i * step, step)
        rows = read_rows(arena, layer, ids)[None, :, :model.mla.row]
        k, v = model.mla_expand(bp, rows)
        seen = (i * step * B + jnp.arange(step * B)) < prefix_len
        top, den, o = model.latent_parts(q, k, v, seen)
        return online_softmax_update(carry, (top, den, o))

    return lax.fori_loop(
        0, (prefix_len + step * B - 1) // (step * B), attend,
        (jnp.zeros(part + (model.mla.v,), jnp.float32),
         jnp.zeros(part, jnp.float32), jnp.full(part, NEG_INF, jnp.float32)))


#: a cached prefix longer than this many positions is WALKED by a softmax
#: layer's suffix prefill, a step of :data:`LATENT_PREFIX_STEP` positions at a
#: time as far as it reaches (one executable whatever its length); up to it
#: the prefix is read whole and meets the suffix in one fusion
DENSE_PREFIX_MAX = 4096


def walks_prefix(table_width: int, block_len: int) -> bool:
    """Whether a suffix prefill over tables of ``table_width`` blocks walks
    its prefix (:data:`DENSE_PREFIX_MAX`): by the tables' static width."""
    return int(table_width) * int(block_len) > DENSE_PREFIX_MAX


def _prefix_parts(model, q, arenas, layer, blocks, prefix_len, positions,
                  window, n_kv):
    """A softmax layer's suffix queries ``q`` (1, H, Ts, D), at ``positions``
    (Ts,), against the CACHED PREFIX of its class, read through the padded
    chain ``blocks`` a step of :data:`LATENT_PREFIX_STEP` positions at a time:
    from the step that holds the first position a ``window`` lets the first
    query see (what the class has let go of behind it is never gathered) as
    far as ``prefix_len`` reaches.  -> the softmax's parts over the prefix,
    as :func:`~bigdl_tpu.nn.attention.online_softmax_update` carries them."""
    from bigdl_tpu.nn.attention import NEG_INF, online_softmax_update
    ka, va = arenas[:2]
    B, d = ka.shape[2], q.shape[-1]
    dv = model.v_dim
    step = min(max(LATENT_PREFIX_STEP // B, 1), blocks.shape[0])
    blocks = jnp.pad(blocks, (0, -blocks.shape[0] % step),
                     constant_values=SCRATCH_BLOCK)
    span = step * B
    part = q.shape[:-1]

    def rows(arena, scale, ids, width, dtype):
        g = read_chain(arena, layer, ids, (B, n_kv, width))
        if scale is not None:       # dequant inside the gather
            g = (g.astype(jnp.float32)
                 * read_chain(scale, layer, ids, (B, n_kv))[..., None])
        return g.transpose(1, 0, 2)[None].astype(dtype)

    def attend(i, carry):
        ids = lax.dynamic_slice_in_dim(blocks, i * step, step)
        k_pos = i * span + jnp.arange(span)
        seen = (window_mask(positions, k_pos, window)
                & (k_pos < prefix_len)[None, :])
        scales = arenas[2:] if len(arenas) == 4 else (None, None)
        return online_softmax_update(carry, model.attend_parts(
            q, rows(ka, scales[0], ids, d, q.dtype),
            rows(va, scales[1], ids, dv, q.dtype), seen))

    first = (0 if window is None
             else jnp.maximum(prefix_len - window + 1, 0) // span)
    return lax.fori_loop(
        first, (prefix_len + span - 1) // span, attend,
        (jnp.zeros(part + (dv,), jnp.float32), jnp.zeros(part, jnp.float32),
         jnp.full(part, NEG_INF, jnp.float32)))


def _prefill_suffix_parts(model, params, ids0, last_index, prefix_len,
                          blocks, *kv, carried=(), h_prev=None):
    """Prefill a prompt SUFFIX against a cached prefix held in paged KV
    blocks: ``ids0`` (1, Ts) is the (bucket-padded) suffix, whose tokens
    live at absolute positions ``prefix_len + i``; ``blocks`` (Pb,) is
    the padded block chain holding the prefix k/v in the pool's arenas
    ``kv`` -- (C, Pb), a row a class, where the pool has several
    (``serving.kvcache.blocks``: a windowed class's row names the scratch
    block where the sequence has let go) -- padded entries point at the
    scratch block and are masked via ``prefix_len``.  Returns (logits at
    suffix index ``last_index``, k, v a class) with k/v (L, 1, H_kv, Ts, D),
    exactly like :func:`_prefill_parts` for the suffix rows.

    Numerics are the offline prefill's: suffix queries attend the SAME
    valid key set (cached prefix keys — stored post-RoPE, so directly
    reusable — plus causal suffix keys) through the same
    ``dot_product_attention`` core, with padded/garbage keys masked to
    the same NEG_INF before the max-subtracted softmax.  Tables wider than
    :data:`DENSE_PREFIX_MAX` positions are WALKED instead
    (:func:`_prefix_parts`), as is every layer with a sink or with values
    of their own width (float32 scores, a K/V head at a time).

    Four arenas of ONE class mark an int8-quantized pool
    (``BlockPool(kv_quant="int8")``): the prefix gather dequantizes
    in-flight (int8 block x per-row scale); the returned suffix k/v stay
    full precision — the engine quantizes them at ``_insert_blocks``.

    ``carried`` is, for a model with recurrent layers, what they hold at
    the prefix's end: ``(state (R, 1, H, D, D), tail (R, 1, taps - 1,
    channels))`` by recurrent layer; the suffix starts from it and the
    result carries, as :func:`_prefill_parts`' does, what they hold at the
    suffix's true end.

    A latent pool's one arena comes alone: a
    latent layer attends its prefix through :func:`_latent_prefix_parts` and
    its own rows causally, one softmax, and hands out its suffix ROWS.
    ``h_prev`` (1, hidden), where the prediction module drafts: the main
    model's hidden state at ``prefix_len - 1``, the first pair's
    (:func:`_mtp_rows`)."""
    from bigdl_tpu.nn.attention import (_finalize, dot_product_attention,
                                        online_softmax_update)

    kv = tuple(a for a in kv if a is not None)
    k_arena = kv[0]
    b, ts = ids0.shape
    B = k_arena.shape[2]
    tabs = blocks if blocks.ndim == 2 else blocks[None]
    pb = tabs.shape[1]
    walk = walks_prefix(pb, B)
    h = params["embed"][ids0]
    positions = prefix_len + jnp.arange(ts)
    if model.pos_encoding == "learned":
        # dynamic gather (clamped for padded tail rows, which stay
        # causally invisible exactly as in the plain bucketed prefill)
        h = h + params["pos"][positions]
    # key validity over the concatenated [prefix | suffix] axis: prefix
    # entries are valid below prefix_len (padded chain entries and the
    # block-padding gap are garbage), suffix entries are causal
    # ... and, on a windowed layer, within the window of the query's
    # ABSOLUTE position (a cached prefix key lives at its index, a suffix
    # key at prefix_len + its index)
    jk = jnp.arange(pb * B + ts)
    kpos = jnp.where(jk < pb * B, jk, prefix_len + jk - pb * B)
    valid = (jk < prefix_len) | (jk >= pb * B)
    masks = ({w: (valid[None, :] & window_mask(positions, kpos, w))[None, None]
              for w in _windows(model)}
             if model.kv_layers and not walk else {})
    own = jnp.arange(ts)

    def prefix(arena, scale, layer, dtype, chain, block):
        # the prefix chain (Pb*B, H, D) -> (1, H, Pb*B, D)
        g = read_chain(arena, layer, chain, block)
        if scale is not None:       # dequant inside the gather
            g = (g.astype(jnp.float32)
                 * read_chain(scale, layer, chain, block[:2])[..., None])
        return g.transpose(1, 0, 2)[None].astype(dtype)

    def layer_fn(spec, h, bp, layer):
        if spec.mixer == "kda":
            y, state, tail = model.layer_kda(
                bp, h, carried[0][layer], carried[1][layer], last_index + 1)
            h, c = _ffn(model, spec, bp, h + y)
            return h, (state, tail), c
        if spec.mixer == "mla":
            q, row, gate = model.mla_inputs(spec, bp, h, positions)
            k, v = model.mla_expand(bp, row)
            o, den, _ = online_softmax_update(
                _latent_prefix_parts(model, bp, q, k_arena, layer, tabs[0],
                                     prefix_len),
                model.latent_parts(q, k, v, window_mask(own, own, None)))
            h, c = _finish_block(model, spec, bp, h,
                                 _finalize(o, den).astype(h.dtype), gate)
            return h, (row,), c
        q, k, v, gate = model.layer_qkv(spec, bp, h, positions)
        cls = model.cache_class(spec)
        arenas = _class_arenas(model, kv, cls)
        sink = model.layer_sink(spec, bp)
        n_kv = k.shape[1]
        if walk or sink is not None or v.shape[-1] != k.shape[-1]:
            # the prefix a step at a time, then the suffix's own keys: one
            # softmax, and the sink's column last
            with jax.named_scope(_attn_scope(model, spec)):
                parts = online_softmax_update(
                    _prefix_parts(model, q, arenas, layer, tabs[cls],
                                  prefix_len, positions, spec.window, n_kv),
                    model.attend_parts(q, k, v,
                                       window_mask(own, own, spec.window)))
            if sink is not None:
                with jax.named_scope("attn/sink"):
                    parts = online_softmax_update(
                        parts, model.sink_parts(
                            sink, (parts[2], parts[1], parts[0])))
            o = _finalize(parts[0], parts[1]).astype(q.dtype)
        else:
            ks, vs = arenas[2:] if len(arenas) == 4 else (None, None)
            kc = jnp.concatenate([prefix(arenas[0], ks, layer, k.dtype,
                                         tabs[cls], (B, n_kv, k.shape[-1])),
                                  k], 2)
            vc = jnp.concatenate([prefix(arenas[1], vs, layer, v.dtype,
                                         tabs[cls], (B, n_kv, v.shape[-1])),
                                  v], 2)
            group = q.shape[1] // k.shape[1]
            if group > 1:       # K/V heads repeated to the query heads
                kc, vc = (jnp.repeat(x, group, axis=1) for x in (kc, vc))
            o = dot_product_attention(q, kc, vc, mask=masks[spec.window])
        h, c = _finish_block(model, spec, bp, h, o, gate)
        return h, (k, v), c

    h, kv_new, state, counts = _scan_prefill(model, params, h, layer_fn)
    h_last = None
    if h_prev is not None:
        kv_new, h_last = _mtp_rows(model, params, h, h_prev, ids0, positions,
                                   kv_new, last_index)
    h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
    h = model._norm(params["ln_f"], h)
    logits = _head_logits(model, params, h)[:, 0]
    return _prefill_result(model, logits, kv_new, state, counts, h_last)


def _insert_blocks(k_arena, v_arena, k_new, v_new, block_ids,
                   k_scale=None, v_scale=None):
    """Scatter a prefilled chunk's k/v (L, 1, H, Tb, D) into the pool's
    arena blocks: row i of the chunk lands in block ``block_ids[i // B]``
    at offset ``i % B`` (chunks always start block-aligned).
    ``block_ids`` is padded to ``ceil(Tb_bucket / B)`` with the scratch
    block, which absorbs the bucket-padding garbage — by the time any
    real position in those rows is attended, decode has overwritten it
    under the position mask.

    With ``k_scale``/``v_scale`` (int8-quantized pool) the chunk rows are
    quantized per (position, head) on the way in and the scale arenas
    are scattered alongside; returns a 4-tuple then."""
    B = k_arena.shape[2]
    nb = block_ids.shape[0]

    def blocks(x):      # (L, 1, H, Tb, D) -> whole blocks (L, nb, B, H, D)
        x = x[:, 0].transpose(0, 2, 1, 3)
        x = jnp.pad(x, ((0, 0), (0, nb * B - x.shape[1]), (0, 0), (0, 0)))
        return x.reshape(x.shape[0], nb, B, *x.shape[2:])

    every = slice(None)
    kb, vb = blocks(k_new), blocks(v_new)
    if k_scale is not None:
        kb, ksb = _kv_quantize_rows(kb)
        vb, vsb = _kv_quantize_rows(vb)
        k_scale = write_rows(k_scale, every, block_ids, None, ksb)
        v_scale = write_rows(v_scale, every, block_ids, None, vsb)
    k_arena = write_rows(k_arena, every, block_ids, None, kb)
    v_arena = write_rows(v_arena, every, block_ids, None, vb)
    if k_scale is not None:
        return k_arena, v_arena, k_scale, v_scale
    return k_arena, v_arena


def _insert_rows(arena, new, block_ids):
    """:func:`_insert_blocks` for a LATENT pool's one arena: a prefilled
    chunk's rows ``new`` (L, 1, Tb, lanes) into the blocks ``block_ids``,
    row i at offset ``i % B`` of block ``block_ids[i // B]``."""
    B, nb = arena.shape[2], block_ids.shape[0]
    x = jnp.pad(new[:, 0], ((0, 0), (0, nb * B - new.shape[2]), (0, 0)))
    return (write_rows(arena, slice(None), block_ids, None,
                       x.reshape(x.shape[0], nb, B, x.shape[2])),)


def _latent_rows(model, spec, bp, h, layer, arenas, positions, blk, off, live,
                 mask, token_mask, kernel=None):
    """A latent layer over the W new rows a slot of a cached step (1: decode;
    the candidate rows of a verify step), ABSORBED: every head's query folded
    through ``W_uk`` meets the one cached row a position, ``W_uv`` after the
    softmax.  ``h`` (S, W, hidden); ``positions`` (S, 1, W) where the rows
    rotate; ``(blk, off)`` (S, W) where they are stored.  ``kernel`` ``None``:
    the live list's walk under ``mask`` (n, W, B) (:func:`_paged_attention`,
    the CPU path and the oracle); else ``(tables, lengths, first)``: the
    Pallas kernel that reads the listed blocks where they lie, the new rows
    written first (``ops.latent_attention``: row i of a slot sees positions
    ``first <= p < lengths + i``).  -> (h, arenas, counts)."""
    m = model.mla
    q, row, gate = model.mla_inputs(spec, bp, h, positions)     # (S, H, W, ..)
    q = model.mla_absorb(bp, q)
    with jax.named_scope("mla/attend"):
        if kernel is not None:
            from bigdl_tpu.ops import latent_decode_attention
            tables, lengths, first = kernel
            arenas = (write_rows(arenas[0], layer, blk, off, row[:, :, None]),)
            u = latent_decode_attention(
                q, arenas[0], tables, lengths, score_dim=m.score_dim,
                layer=layer, value_lanes=m.kv_rank, first=first)
        else:
            u, arenas = _paged_attention(q, row[:, None], None, arenas, layer,
                                         blk, off, live, mask,
                                         score_dim=m.score_dim)
    o = model.mla_values(bp, u[..., :m.kv_rank])
    h, counts = _finish_block(model, spec, bp, h, o.astype(h.dtype), gate,
                              token_mask=token_mask)
    return h, arenas, counts


def decode_attention_path(model, pool, requested: str = "auto") -> str:
    """How the paged step programs read a pool's listed blocks: ``"gather"``
    -- :func:`_paged_attention`'s walk, the XLA path, every model's CPU path
    and the kernels' oracle -- or ``"paged_kernel"``, the Pallas kernel of
    the pool's KIND that reads them where they lie (``ops.latent_attention``
    for a latent pool, ``ops.grouped_attention`` for a ``(k, v)`` pool).
    Resolved once, from the platform and the pool's shapes; ``requested`` is
    the engine's ``decode_attn``.

    An int8 pool is gathered (the kernels read raw blocks).  ``"auto"`` is
    the kernel on a TPU where the pool is latent, or its softmax layers'
    query heads share K/V heads, carry a sink or have values of their own
    width, and the compiled kernel takes the pool's geometry; the walk
    otherwise (a head a query head: no cell says the kernel beats it
    there).  ``"paged_kernel"`` is the kernel, and off the interpreter a
    geometry the compiled kernel cannot take raises."""
    from bigdl_tpu.ops import _pallas
    from bigdl_tpu.ops.grouped_attention import check_grouped_kernel_shapes
    from bigdl_tpu.ops.latent_attention import check_latent_kernel_shapes

    if requested not in ("auto", "gather", "paged_kernel"):
        raise ValueError(f"decode_attn must be 'auto', 'gather' or "
                         f"'paged_kernel', got {requested!r}")
    if pool.kv_quant is not None:
        if requested == "paged_kernel":
            raise ValueError(
                "kv_quant='int8' requires decode_attn='gather' (the "
                "Pallas paged kernel reads raw blocks)")
        return "gather"
    if requested == "gather":
        return "gather"

    def check_shapes():
        if pool.latent:
            return check_latent_kernel_shapes(pool.block_len, pool.shape[-1],
                                              pool.dtype)
        for c in pool.classes:
            check_grouped_kernel_shapes(
                pool.block_len, c.shape[-1], c.head_dim, pool.dtype, c.v_dim,
                c.n_heads, c.v_shape[-1])

    compiled = not _pallas.use_interpret()
    if requested == "paged_kernel":
        if compiled:
            check_shapes()
        return "paged_kernel"
    specs = [s for _, period in model.plan for s in period
             if s.mixer == "attention"]
    shared = any(s.n_head != model.kv_heads(s) or s.sink
                 for s in specs) or model.v_dim != model.head_dim
    if not compiled or not (pool.latent or shared):
        return "gather"
    try:
        check_shapes()
    except ValueError:
        return "gather"
    return "paged_kernel"


def _reads_in_place(attn_impl: str) -> bool:
    """Whether a step reads through the pool kind's kernel: what
    :func:`decode_attention_path` resolved, the step functions' one
    ``attn_impl``."""
    if attn_impl not in ("gather", "paged_kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'paged_kernel', "
                         f"got {attn_impl!r}")
    return attn_impl == "paged_kernel"


def _decode_step_paged(model, params, token, pos, live, *arenas,
                       table_width: Optional[int] = None,
                       attn_impl: str = "gather"):
    """One cached decode step over S slots against PAGED caches: same
    contract as :func:`_decode_step_slots`, but each slot's KV lives in
    pool blocks, and the step reads the blocks the round's **live list**
    names: ``live`` (3, n) int32 -- which block, whose, where in its
    chain (``serving.kvcache.blocks.live_list``) -- holds what the
    active slots hold up to their write positions, padded with nobody's
    entries to a fixed length (the engine's: every entry of every
    ``table_width``-wide table), so this stays ONE AOT executable
    regardless of sequence lengths, and attention walks the list a chunk
    at a time as far as it holds blocks (:func:`_paged_attention`): a
    full pool reads every chunk, a round of short chains one.  The new
    k/v scatter by (block, offset) derived from ``pos`` and the list;
    attention reads each slot's blocks under the identical position mask
    / score math as the slot engine -- either by gathering the listed
    blocks (``attn_impl="gather"``, the XLA path) or in place via the
    Pallas kernel of the pool's kind over the tables the list spells
    (``attn_impl="paged_kernel"``, which needs their ``table_width``;
    :func:`decode_attention_path` settles which).  The arenas (the pool's
    layout, ``serving.kvcache.blocks``) are donated by the serving engine
    and carried whole through the layer loop (:func:`_scan_layers`).

    ``arenas`` are the pool's ``(k, v)`` or, int8
    (``BlockPool(kv_quant="int8")``), ``(k, v, k_scale, v_scale)``: the new
    k/v row is quantized per (slot, head) on write and the gather
    dequantizes in-flight.  Behind them, for a model
    with recurrent layers, ride the state arenas ``(state, tail)``
    (``serving.kvcache.state``: a row a recurrent layer and slot): a
    recurrent layer reads and writes its slots' rows (``kda/step``) and an
    idle slot's row stays as it was.  A LATENT pool's arenas are its one
    ``(rows,)``: a latent layer writes the new row and attends ABSORBED
    (``TransformerLM.mla_absorb`` / ``mla_values`` around ``mla/attend``:
    :func:`_paged_attention`'s walk, the CPU path and the oracle, or under
    ``attn_impl="paged_kernel"`` the Pallas kernel that reads the listed
    blocks where they lie, ``ops.latent_attention``).

    -> (logits (S, V) float32, [the routed layers' integers, when the
    model has any], *arenas).  The serving engine's decode program is
    :func:`_decode_pick_paged`, which picks from these logits on the
    device and hands out ids; the logits stay this function's result for
    what reads them (the tests, the pick's twin on the host)."""
    kernel = _reads_in_place(attn_impl)
    kv = _split_arenas(model, arenas)[0]
    classes = model.cache_classes
    s = token.shape[0]
    B = kv[0].shape[2]
    h = params["embed"][token][:, None, :]
    if model.pos_encoding == "learned":
        h = h + params["pos"][pos][:, None, :]
    positions = pos[:, None, None]
    # the list a CLASS of softmax layers (one class, a latent pool: the list
    # itself): a class with a window lists its window's blocks alone
    lives = split_live(live, s, table_width,
                       [c.window for c in classes] or [None], B)

    def reads(live):
        # what a class's layers read by: (the masks by window (n, 1, B), the
        # block and offset of each slot's write position (S, 1), the slots
        # that hold a listed block, the tables the list spells)
        ids, owner, where = live
        held = ((owner[None, :] == jnp.arange(s)[:, None])
                & (ids != SCRATCH_BLOCK)[None, :])
        # the block holding each slot's write position (an idle slot's
        # garbage write lands in the scratch block 0 and is never attended);
        # one new row a slot: (S, 1)
        blk = jnp.max(jnp.where(held & (where[None, :] == (pos // B)[:, None]),
                                ids[None, :], 0), axis=1)[:, None]
        tables = None
        if kernel:
            # the kernel walks (S, M) tables: the list spelled out (padded
            # entries, owned by nobody, drop; what a windowed class has let
            # go of stays the scratch block, behind the window's mask)
            tables = jnp.zeros((s, table_width), jnp.int32).at[
                owner, where].set(ids, mode="drop")
        return (_list_masks(model, live, pos[:, None], B), blk,
                jnp.any(held, axis=1), tables)

    by_class = [reads(x) for x in lives]
    masks, blk, active, tables = by_class[0]
    # a slot that holds no listed block but scratch padding is idle: its
    # token is routed to no expert (its other rows are garbage that nothing
    # reads)
    off = (pos % B)[:, None]

    def layer_fn(spec, h, bp, layer, arenas):
        kv, recurrent = _split_arenas(model, arenas)
        if spec.mixer == "kda":
            state, tail = recurrent
            y, state, tail_row = model.layer_kda_step(
                bp, h, state, tail[layer], layer, active)
            recurrent = (state, tail.at[layer].set(tail_row))
            h, counts = _ffn(model, spec, bp, h + y, active[:, None])
        elif spec.mixer == "mla":
            h, kv, counts = latent_layer(spec, h, bp, layer, kv)
        else:
            c = model.cache_class(spec)
            h, mine, counts = attention_layer(
                spec, h, bp, layer, _class_arenas(model, kv, c), lives[c],
                by_class[c])
            kv = _with_class(kv, c, mine)
        return h, kv + recurrent, counts

    def latent_layer(spec, h, bp, layer, arenas):
        # ABSORBED, one new row a slot: the listed blocks read where they
        # lie (the kernel), or the live list's walk
        return _latent_rows(
            model, spec, bp, h, layer, arenas, positions, blk, off, live,
            masks[None], active[:, None],
            (tables, jnp.where(active, pos + 1, 0), 0) if kernel else None)

    def attention_layer(spec, h, bp, layer, arenas, live, read):
        masks, blk, _, tables = read
        q, k, v, gate = model.layer_qkv(spec, bp, h, positions)  # (S, H, 1, D)
        sink = model.layer_sink(spec, bp)
        if kernel:
            # in-place block reads via the table (no dense gather), the new
            # rows first
            from bigdl_tpu.ops import grouped_decode_attention
            arenas = tuple(
                write_rows(a, layer, blk, off, x.transpose(0, 2, 1, 3))
                for a, x in zip(arenas, (k, v)))
            with jax.named_scope(_attn_scope(model, spec)):
                o = grouped_decode_attention(
                    q, *arenas, tables, jnp.where(active, pos + 1, 0),
                    layer=layer, n_kv_head=model.kv_heads(spec),
                    window=spec.window, sink=sink, v_dim=v.shape[-1])
        else:
            with jax.named_scope(_attn_scope(model, spec)):
                o, arenas = _paged_attention(q, k, v, arenas, layer, blk,
                                             off, live, masks[spec.window],
                                             sink=sink)
        h, counts = _finish_block(model, spec, bp, h, o.astype(h.dtype), gate,
                                  token_mask=active[:, None])
        return h, arenas, counts

    h, arenas, counts = _scan_layers(model, params, h, arenas, layer_fn)
    h = model._norm(params["ln_f"], h)
    logits = _head_logits(model, params, h)[:, 0].astype(jnp.float32)
    if model.moe_layers:
        # the routed layers' integers ride out beside the logits
        return (logits, counts) + arenas
    return (logits,) + arenas


def pick_next(logits, key, temperature):
    """THE sampling rule of a decode step, on the device: ``logits``
    (n, V) float32, one key, one temperature -> (n,) ids, 0-based.
    Greedy argmax (the first index on ties, as ``np.argmax``) at
    temperature 0, else the key's categorical draw over the rows at
    shape (n, V).  The offline scan draws a whole batch under one key;
    the serving step (:func:`pick_rows`) one slot at a time at (1, V),
    which is what ``serving.spec.verify.pick_token`` draws on the host."""
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(key, logits / jnp.maximum(
        temperature, 1e-6), axis=-1)
    return jnp.where(temperature > 0.0, sampled, greedy)


def pick_rows(logits, temperature, keys):
    """:func:`pick_next` a slot: ``logits`` (S, V) float32, ``temperature``
    (S,) float32 and ``keys`` (S, 2) uint32 (zeros for a greedy slot)
    -> (S,) int32.  The noise is drawn only in a round that samples: the
    step sees that in the temperatures it is handed."""
    def drawn(_):
        return jax.vmap(lambda row, key, t: pick_next(row[None, :], key, t)[0])(
            logits, keys, temperature)

    def greedy(_):
        return jnp.argmax(logits, axis=-1)

    return lax.cond(jnp.any(temperature > 0.0), drawn, greedy,
                    None).astype(jnp.int32)


def _decode_pick_paged(model, params, token, pos, live, temperature, keys,
                       prev_ids, *kv, **kw):
    """:func:`_decode_step_paged` with the pick applied on the device: the
    serving engine's decode program.  -> (ids (S,) int32, [the routed
    layers' counts], *arenas): no output has the vocabulary's width, so a
    round hands the host S integers.  ``prev_ids`` (S,) int32 is what the
    previous call returned, still on the device: a slot whose ``token`` is
    negative takes its entry of it, so a round can be enqueued before its
    predecessor's ids have reached the host."""
    token = jnp.where(token < 0, prev_ids, token)
    logits, *rest = _decode_step_paged(model, params, token, pos, live, *kv,
                                       **kw)
    return (pick_rows(logits, temperature, keys), *rest)


def _verify_step_paged(model, params, tokens, pos, n_cand, tables, *kv):
    """Speculative VERIFY over paged caches: score all W = k+1 candidate
    rows per slot in one fixed-shape step.  ``tokens`` (S, W) int32
    0-based — row layout ``[last_emitted, draft_1 .. draft_k]`` — and
    ``pos`` (S,) is each slot's next write position, so candidate j sits
    at absolute position ``pos + j``.  ``n_cand`` (S,) int32 counts the
    VALID rows per slot (1 for a plain-decode slot, 0 for an idle slot);
    padded rows' k/v writes are redirected to the scratch block so they
    can never touch a live position.  Returns (logits (S, W, V) f32,
    arenas') — logits row j is the target distribution for the token
    AFTER candidate j, i.e. exactly what ``_decode_step_paged`` would
    have produced had rows 0..j been fed one at a time.

    Rollback is pointer-only: a rejected row's k/v stays in the arena as
    garbage ABOVE the slot's rewound position pointer, where the
    position mask (`<= pos + j`) hides it until a later write overwrites
    that offset — the same stale-row invariant the plain decode step
    already relies on for recycled blocks.  Attention always uses the
    dense gather (the Pallas paged kernel is single-query); it IS
    ``_decode_step_paged``'s gather branch (:func:`_paged_attention`),
    so emitted streams stay token-exact with every decode_attn
    setting."""
    w = tokens.shape[1]
    kv = tuple(a for a in kv if a is not None)
    abspos = pos[:, None] + jnp.arange(w)[None, :]   # (S, W)
    # the tables a class (C, S, M) where the pool has several
    tables = tables if tables.ndim == 3 else tables[None]
    lives = [table_list(t) for t in tables]
    # row j attends positions <= pos + j: (n, W, B) over the tables' entries
    masks = [_list_masks(model, live, abspos, kv[0].shape[2])
             for live in lives]
    return _verify_rows(model, params, tokens, n_cand, tables, lives, kv,
                        store=abspos, rope=abspos, masks=masks)


def _verify_rows(model, params, tokens, n_cand, tables, lives, arenas, *,
                 store, rope, masks):
    """The body linear and tree verify share: row j of slot s is stored
    at arena offset ``store[s, j]``, rotated at position ``rope[s, j]``
    and attends, in a layer of class ``c``, the entries of ``lives[c]``
    (``tables[c]`` as a list) under ``masks[c][window of the layer]`` (n, W,
    B)."""
    s, w = tokens.shape
    m = tables.shape[2]
    B = arenas[0].shape[2]
    h = params["embed"][tokens]                      # (S, W, hidden)
    if model.pos_encoding == "learned":
        # clamp: padded rows of a near-full slot may index past the table
        h = h + params["pos"][jnp.minimum(rope, params["pos"].shape[0] - 1)]
    # (S, 1, W): broadcasts against (S, H, W, half) inside apply_rope
    positions = rope[:, None, :]
    # scatter targets: row j writes block tables[s, store // B] at offset
    # store % B.  Two safety redirects: the column index clamps to the
    # table width (a padded row of a chain-filling slot would otherwise
    # gather-clamp onto the LAST real block), and rows >= n_cand go to
    # the scratch block outright.
    blkcol = jnp.minimum(store // B, m - 1)
    valid = jnp.arange(w)[None, :] < n_cand[:, None]
    blks = [jnp.where(valid, t[jnp.arange(s)[:, None], blkcol], 0)
            for t in tables]                                     # (S, W) each
    off = store % B

    def layer_fn(spec, h, bp, layer, arenas):
        if spec.mixer == "mla":     # W candidate rows a slot, absorbed
            return _latent_rows(model, spec, bp, h, layer, arenas, positions,
                                blks[0], off, lives[0], masks[0][None], valid)
        c = model.cache_class(spec)
        q, k, v, gate = model.layer_qkv(spec, bp, h, positions)  # (S, H, W, D)
        o, mine = _paged_attention(q, k, v, _class_arenas(model, arenas, c),
                                   layer, blks[c], off, lives[c],
                                   masks[c][spec.window],
                                   sink=model.layer_sink(spec, bp))
        h, counts = _finish_block(model, spec, bp, h, o.astype(h.dtype), gate,
                                  token_mask=valid)
        return h, _with_class(arenas, c, mine), counts

    h, arenas, _ = _scan_layers(model, params, h, arenas, layer_fn)
    h = model._norm(params["ln_f"], h)
    logits = _head_logits(model, params, h)      # (S, W, V)
    return (logits.astype(jnp.float32),) + arenas


def _selfdraft_step_paged(model, params, tokens, pos, n_cand, fresh,
                          temperature, keys, hid, live, *arenas,
                          table_width: int, attn_impl: str = "gather",
                          with_logits: bool = False):
    """One SELF-DRAFTING round over a latent pool: the model's own prediction
    module (``TransformerLM.mtp``) is the drafter, through the target's pool
    (its block's rows are the arena's last layer, :func:`_mtp_rows`' layout).
    ``tokens`` (S, 2) are ``[x, d]``: a slot's last emitted token, at position
    ``pos``, and the draft for ``pos + 1``; ``n_cand`` (S,) 2 where the draft
    is to be verified, 1 for a plain row, 0 for an idle slot.

    1. VERIFY: the main model scores both rows (W = 2, absorbed, rows written
       at ``pos`` and ``pos + 1``), and the step picks ``y0`` and ``y1`` from
       them (:func:`pick_rows`, the slot's step keys ``keys[:, 0]`` and ``[:,
       1]``); ``accepted = (y0 == d)``: the slot emits ``y0``, and ``y1`` too
       if accepted.  A rejected row is a pointer rewind: it stays above the
       slot's position, masked until overwritten
       (:func:`_verify_step_paged`'s invariant).
    2. DRAFT: the module runs over the NEW pairs -- (hidden at ``pos``, y0)
       and, if accepted, (hidden at ``pos + 1``, y1); for a ``fresh`` slot,
       seated by a prefill, the pair before them first, (``hid``: the
       prefill's hidden state at ``pos - 1``, x) -- writes their rows one
       position on in its arena layer, attends ABSORBED over the pairs before
       (positions 1 ..), and the next round's draft is picked from the last
       valid pair's logits under the key that will verify it (``keys[:, 2 +
       accepted]``).

    ``hid`` (S, hidden) is read for fresh slots alone.  ``live``: the round's
    live list, reaching the block of ``pos + 2``.  -> (``out`` (S, 4) int32
    ``[y0, y1, accepted, draft]``, [the routed layers' integers, the module's
    block's among them], *arenas[, the logits (S, 2, V) and the draft logits
    (S, V) ``with_logits``: what the tests read]): no output of a serving
    round has the vocabulary's width."""
    kernel = _reads_in_place(attn_impl)
    s, w = tokens.shape
    B = arenas[0].shape[2]
    mtp_layer = len(model.latent_layers)
    ids, owner, where = live
    tables = jnp.zeros((s, table_width), jnp.int32).at[owner, where].set(
        ids, mode="drop")
    j = jnp.arange(w)
    active = n_cand > 0
    k_pos = where[:, None] * B + jnp.arange(B)[None, :]

    def place(store, ok):
        # where a row is stored; one that is not to be kept, in scratch
        blk = tables[jnp.arange(s)[:, None],
                     jnp.minimum(store // B, table_width - 1)]
        return jnp.where(ok, blk, SCRATCH_BLOCK), store % B

    def attend(store, first):
        # (mask of the walk, operands of the kernel): row i of a slot sees
        # the positions ``first <= p <= store[:, i]``
        if kernel:
            return None, (tables, jnp.where(active, store[:, 0] + 1, 0), first)
        return (_list_masks(model, live, store, B)[None]
                & (k_pos >= first)[:, None, :]), None

    # -- 1. verify ------------------------------------------------------------
    abspos = pos[:, None] + j[None, :]
    valid = j[None, :] < n_cand[:, None]
    blk, off = place(abspos, valid)
    mask, operands = attend(abspos, 0)
    h = params["embed"][tokens]                             # (S, W, hidden)

    def layer_fn(spec, h, bp, layer, arenas):
        return _latent_rows(model, spec, bp, h, layer, arenas,
                            abspos[:, None, :], blk, off, live, mask, valid,
                            operands)

    h, arenas, counts = _scan_layers(model, params, h, arenas, layer_fn)
    logits = _head_logits(model, params, model._norm(params["ln_f"], h)
                          ).astype(jnp.float32)             # (S, W, V)
    y0 = pick_rows(logits[:, 0], temperature, keys[:, 0])
    y1 = pick_rows(logits[:, 1], temperature, keys[:, 1])
    accepted = (n_cand == 2) & (y0 == tokens[:, 1])
    # -- 2. draft -------------------------------------------------------------
    hs = jnp.where(fresh[:, None, None],
                   jnp.stack([hid.astype(h.dtype), h[:, 0]], axis=1), h)
    toks = jnp.where(fresh[:, None], jnp.stack([tokens[:, 0], y0], axis=1),
                     jnp.stack([y0, y1], axis=1))
    store = abspos + jnp.where(fresh, 0, 1)[:, None]
    n_pairs = jnp.where(active, jnp.where(fresh, 2, 1 + accepted), 0)
    pvalid = j[None, :] < n_pairs[:, None]
    pblk, poff = place(store, pvalid)
    pmask, poperands = attend(store, 1)
    z = model.mtp_embed(params, hs, toks)
    with jax.named_scope("mtp/block"):
        g, arenas, c = _latent_rows(
            model, model.mtp, params["mtp"]["block"], z, mtp_layer, arenas,
            (store - 1)[:, None, :], pblk, poff, live, pmask, pvalid,
            poperands)
    counts = counts + c
    last = jnp.clip(n_pairs - 1, 0, w - 1)
    g = jnp.take_along_axis(g, last[:, None, None], axis=1)[:, 0]
    draft_logits = model.mtp_logits(params, g).astype(jnp.float32)  # (S, V)
    draft_key = jnp.take_along_axis(
        keys[:, 2:4], accepted.astype(jnp.int32)[:, None, None], axis=1)[:, 0]
    draft = pick_rows(draft_logits, temperature, draft_key)
    out = (jnp.stack([y0, y1, accepted.astype(jnp.int32), draft], axis=1),)
    if model.moe_layers or model.mtp.mlp == "moe":
        out += (counts,)
    out += tuple(arenas)
    return out + (logits, draft_logits) if with_logits else out


def _tree_verify_step_paged(model, params, tokens, pos, n_cand, tables,
                            k_arena, v_arena, k_scale=None, v_scale=None,
                            *, depths, anc):
    """Tree-speculative VERIFY over paged caches: score all W nodes of a
    fixed-shape candidate TREE per slot in one step.  ``tokens`` (S, W)
    holds one token per tree node (node 0 = the last emitted root, the
    shape's topological order), ``depths`` (W,) and ``anc`` (W, W) are
    the shape's static per-node depths and ancestor-or-self matrix —
    baked into the trace, one executable per shape.

    Node j stores its k/v at arena offset ``pos + j`` (a unique slot per
    node — siblings share a POSITION but never an offset) while RoPE
    rotates it at its TRUE position ``pos + depths[j]``, and its mask
    admits the committed prefix (``col < pos``) plus exactly its
    ancestor offsets.  A path node at depth d therefore attends the same
    (position, key) set as linear-verify row d — the same body
    (:func:`_verify_rows`), so logits along any root-to-leaf path are
    bit-identical to ``_verify_step_paged`` scoring that path as a
    chain, and for chain shapes (``anc`` lower-triangular, ``depths[j]
    == j``) the whole step IS the linear verify.  After the host walk
    accepts a path, ``_tree_commit_paged`` copies accepted OFF-SPINE
    rows down to their position offsets; rejected rows are garbage above
    the rewound pointer exactly as in linear verify.  Rows >= ``n_cand``
    (lower-rung or plain slots riding a wider executable) scatter to the
    scratch block."""
    w = tokens.shape[1]
    B = k_arena.shape[2]
    depths = jnp.asarray(depths, jnp.int32)          # (W,) static
    ancm = jnp.asarray(np.asarray(anc), bool)        # (W, W) static
    store = pos[:, None] + jnp.arange(w)[None, :]    # (S, W) arena offsets
    rope = pos[:, None] + depths[None, :]            # (S, W) true positions
    live = table_list(tables)
    # node j attends the committed prefix (col < pos) plus the offsets of
    # its ancestors-or-self (col == pos + i with anc[j, i]): (n, W, B), an
    # entry's columns against its owner's position
    col = live[2][:, None] * B + jnp.arange(B)[None, :]
    rel = col - pos[live[1]][:, None]                # (n, B)
    in_tree = (rel >= 0) & (rel < w)
    anc_cols = ancm[:, jnp.clip(rel, 0, w - 1)]      # (W, n, B)
    mask = ((rel < 0)[:, None, :]
            | (in_tree[:, None, :] & jnp.moveaxis(anc_cols, 0, 1)))
    if _windows(model) != {None}:
        raise NotImplementedError(
            "tree verify stores a node away from its position; a windowed "
            "layer's mask over such offsets is not written")
    return _verify_rows(model, params, tokens, n_cand, tables[None], [live],
                        _arenas(k_arena, v_arena, k_scale, v_scale),
                        store=store, rope=rope, masks=[{None: mask}])


def _tree_commit_paged(src, pos, tables, k_arena, v_arena,
                       k_scale=None, v_scale=None, *, n_heads=None):
    """Pointer-rewind's tree counterpart: after the host walk accepts a
    path, copy each accepted node's k/v row from its STORE offset
    ``pos + src[s, d-1]`` down to its POSITION offset ``pos + d`` so the
    committed chain reads contiguously for every later step.  ``src``
    (S, Dmax) int32 gives the accepted node index at depth d = column+1;
    the identity ``src[s, d-1] == d`` (spine nodes, plain slots, idle
    rows) degenerates to a same-location rewrite, so only rounds where
    some slot accepted an ALTERNATE need to run this at all — the engine
    skips the call otherwise.  Gathers complete before scatters
    (functional update), so an identity row can never read a
    half-written block.  Rows move whole, all layers at once: a data
    row as the one "head" its lanes are; a quantized pool's scale rows
    need ``n_heads``."""
    s, dmax = src.shape
    m = tables.shape[1]
    B = k_arena.shape[2]
    rowsel = jnp.arange(s)[:, None]
    src_abs = pos[:, None] + src
    dst_abs = pos[:, None] + 1 + jnp.arange(dmax)[None, :]
    # identity rows of a near-full slot clamp src and dst to the SAME
    # final block column, so the clamped write is still a no-op
    sblk = tables[rowsel, jnp.minimum(src_abs // B, m - 1)]
    soff = src_abs % B
    dblk = tables[rowsel, jnp.minimum(dst_abs // B, m - 1)]
    doff = dst_abs % B
    every = slice(None)

    def move(arena, block):
        # each source row's whole block: (L, S, Dmax, B, ..)
        g = read_chain(arena, every, sblk[..., None], block)
        rows = g[:, rowsel, jnp.arange(dmax)[None, :], soff]
        return write_rows(arena, every, dblk, doff, rows)

    out = (move(k_arena, (B, 1, k_arena.shape[3])),
           move(v_arena, (B, 1, v_arena.shape[3])))
    if k_scale is not None:
        out += (move(k_scale, (B, n_heads)), move(v_scale, (B, n_heads)))
    return out


def _decode_step(model, params, token, pos, k_cache, v_cache):
    """One cached decode step for a homogeneous batch: token (B,)
    0-based, pos scalar index of the position being *written* (one
    prompt batch decodes in lockstep).  A batch row IS a slot whose
    position happens to equal every other row's."""
    b = token.shape[0]
    return _decode_step_slots(model, params, token,
                              jnp.full((b,), pos, dtype=jnp.int32),
                              k_cache, v_cache)


def _decode_scan(model, params, max_new, first_token, pos0,
                 k_cache, v_cache, rng, temperature):
    """max_new cached steps under one scan.  first_token is 0-based.
    Jitted per model by :func:`_offline_programs`."""
    from bigdl_tpu.quant import dequantize_entry
    params = dequantize_entry(params)

    def step(carry, key):
        token, pos, kc, vc = carry
        logits, kc, vc = _decode_step(model, params, token, pos, kc, vc)
        nxt = pick_next(logits, key, temperature)
        return (nxt, pos + 1, kc, vc), nxt

    keys = jax.random.split(rng, max_new)
    (_, _, _, _), out = lax.scan(
        step, (first_token, pos0, k_cache, v_cache), keys)
    return out.T  # (B, max_new), 0-based


def _offline_programs(model):
    """The jitted offline prefill and decode scan of ``model``, kept on the
    module itself (``_jit_cache``, beside its jitted apply).  A module-level
    jit with the module as a static argument would hold every model that
    ever generated — and the weights on its shell — in jax's process-wide
    cache until ``jax.clear_caches()``; kept here, they go when it goes."""
    fns = model._jit_cache.get("generate")
    if fns is None:
        fns = model._jit_cache["generate"] = (
            jax.jit(functools.partial(_prefill, model), static_argnums=(2,)),
            jax.jit(functools.partial(_decode_scan, model),
                    static_argnums=(1,)))
    return fns


def generate(model: TransformerLM, params, prompt_ids, max_new_tokens: int,
             *, temperature: float = 0.0, rng=None, cache_len: Optional[int] = None):
    """Generate ``max_new_tokens`` continuations of ``prompt_ids`` (B, T)
    1-based ids.  temperature=0 -> greedy argmax; >0 -> softmax sampling
    driven by ``rng``.  Returns (B, T + max_new_tokens) 1-based ids.

    ``cache_len`` defaults to prompt+new (must be <= model.max_len —
    positions beyond the table would silently clamp otherwise)."""
    ids = jnp.asarray(prompt_ids)
    if jnp.issubdtype(ids.dtype, jnp.floating):
        ids = ids.astype(jnp.int32)
    b, t = ids.shape
    if t == 0:
        raise ValueError("empty prompt: generation needs at least one "
                         "prompt token")
    if max_new_tokens <= 0:
        return ids
    total = t + int(max_new_tokens)
    cache_len = int(cache_len) if cache_len is not None else total
    if cache_len > model.max_len or total > model.max_len:
        raise ValueError(
            f"prompt + new tokens ({total}) exceeds the model's max_len "
            f"({model.max_len})")
    if cache_len < total:
        # dynamic_update_slice CLAMPS out-of-range starts: steps past the
        # cache end would silently overwrite the last slot and corrupt
        # the decode (no sliding-window attention is implemented)
        raise ValueError(
            f"cache_len ({cache_len}) smaller than prompt + new tokens "
            f"({total})")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    ids0 = ids - 1
    prefill, decode_scan = _offline_programs(model)
    logits, k_cache, v_cache = prefill(params, ids0, cache_len)
    greedy = jnp.argmax(logits, axis=-1)
    if temperature > 0.0:
        rng, sub = jax.random.split(rng)
        first = jax.random.categorical(sub, logits / temperature, axis=-1)
    else:
        first = greedy
    if max_new_tokens == 1:
        return jnp.concatenate([ids, first[:, None] + 1], axis=1)
    rest = decode_scan(params, int(max_new_tokens) - 1,
                       first, jnp.int32(t), k_cache, v_cache, rng,
                       jnp.float32(temperature))
    out = jnp.concatenate([first[:, None], rest], axis=1)
    return jnp.concatenate([ids, out + 1], axis=1)
