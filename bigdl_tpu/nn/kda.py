"""Gated delta-rule linear attention with a per-channel decay (KDA, the
Kimi-Linear form): a layer that keeps no keys and values, only a state
``S`` in R^{d_k x d_v} a head, float32::

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d_k)

``g_t`` in R^{d_k} is the log of a decay in (0, 1] a channel, ``beta_t`` in
(0, 2) (above 1 the transition has a negative eigenvalue).  Two forms of
that one function, plain ``jax.numpy``, usable outside the LM:

- :func:`kda_step`: one position, state in -> state out (the decode path);
- :func:`kda_chunked`: a whole sequence in chunks of a constant length.
  Inside a chunk the delta rule in its WY / UT-transform form (matrix
  products and ONE triangular solve for every chunk at once), across
  chunks a ``lax.scan`` that carries ``S``.

and the depthwise causal convolution that feeds them (:func:`short_conv`,
:func:`short_conv_step`).  Everything is computed in float32 at the highest
matmul precision: the state lives for thousands of positions, and a
rounding in it stays.

The in-chunk terms need ``exp(G_t - G_i)`` for every pair ``i <= t`` of a
chunk (``G`` the running sum of ``g``).  The usual factoring ``(q *
e^{G_t})(k * e^{-G_i})`` overflows float32 once a channel forgets by
``e^{-88}`` inside a chunk (13 steps at a decay of 1e-3 a step), so the
chunk is cut into sub-chunks of :data:`SUB` positions: a pair in different
sub-chunks is factored through the START of the later one (both exponents
are sums of ``g`` and so never positive), a pair inside one sub-chunk is
taken in log space pair by pair.  Nothing is ever raised to a positive
power; what underflows is a term that small.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

#: positions of a chunk (the scan's step) and of a sub-chunk (see above)
CHUNK = 64
SUB = 16

_HI = lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state):
    """One position of the recurrence, any leading axes: ``q``, ``k``, ``g``
    (..., d_k), ``v`` (..., d_v), ``beta`` (...,), ``state`` (..., d_k, d_v)
    float32 -> (o (..., d_v) float32, state').  A position with ``beta = 0``
    and ``g = 0`` hands the state back as it was."""
    with jax.named_scope("kda/step"):
        q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
        decayed = state * jnp.exp(g)[..., None]
        # elementwise products and f32 sums (a matrix-vector product a head
        # fills nothing of the matrix unit, and its default precision on the
        # TPU rounds the state to bfloat16)
        seen = jnp.sum(decayed * k[..., None], axis=-2)
        state = decayed + k[..., None] * (beta[..., None] * (v - seen))[..., None, :]
        o = jnp.sum(state * q[..., None], axis=-2) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        return o, state


def _masked(g, beta, valid):
    """An invalid position decays nothing and writes nothing."""
    if valid is None:
        return g, beta
    return (jnp.where(valid[:, :, None, None], g, 0.0),
            jnp.where(valid[:, :, None], beta, 0.0))


def kda_chunked(q, k, v, g, beta, state=None, valid=None, *, chunk: int = CHUNK,
                sub: int = SUB):
    """The recurrence over whole sequences: ``q``, ``k``, ``g`` (B, T, H,
    d_k), ``v`` (B, T, H, d_v), ``beta`` (B, T, H); ``state`` (B, H, d_k,
    d_v) float32 to start from (``None``: zeros); ``valid`` (B, T) bool
    (``None``: every position): an invalid position leaves the state as it
    was (its output is unspecified).  -> (o (B, T, H, d_v) float32, the
    state after the last position)."""
    if chunk % sub:
        raise ValueError(f"chunk ({chunk}) must be a multiple of sub ({sub})")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    g, beta = _masked(g, beta, valid)
    pad = -t % chunk
    if pad:     # positions past the end are invalid ones
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    n, c, m = (t + pad) // chunk, chunk, chunk // sub
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def chunks(x):      # (B, T, H, ..) -> (B, H, n, C, ..)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    with jax.named_scope("kda/chunk_scan"):
        q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
        G = jnp.cumsum(g, axis=3)                           # (B, H, n, C, dk)
        # the sum of g before each sub-chunk, and each position's own sum
        # from its sub-chunk's start on (<= 0)
        before = jnp.concatenate(
            [jnp.zeros_like(G[..., :1, :]), G[..., sub - 1:c - 1:sub, :]], axis=3)
        rel = (G.reshape(b, h, n, m, sub, dk) - before[..., None, :])
        q_rel = (q.reshape(rel.shape) * jnp.exp(rel))      # rows, by sub-chunk
        k_rel = (k.reshape(rel.shape) * jnp.exp(rel))
        # columns of EARLIER sub-chunks, decayed up to sub-chunk a's start:
        # (B, H, n, m, C, dk); zero for a column that is not earlier
        earlier = (jnp.arange(c)[None, :] < (jnp.arange(m) * sub)[:, None])
        to_start = before[..., :, None, :] - G[..., None, :, :]
        k_before = k[..., None, :, :] * jnp.exp(
            jnp.where(earlier[..., None], to_start, -jnp.inf))
        off = lambda rows: jnp.einsum(                      # noqa: E731
            "...atd,...aid->...ati", rows, k_before, precision=_HI
        ).reshape(b, h, n, c, c)
        # pairs inside one sub-chunk, in log space pair by pair
        i_le_t = jnp.tril(jnp.ones((sub, sub), bool))
        pair = jnp.exp(jnp.where(i_le_t[..., None],
                                 rel[..., :, None, :] - rel[..., None, :, :],
                                 -jnp.inf))                 # (.., m, t, i, dk)
        k_sub = k.reshape(rel.shape)

        def diag(rows):     # (.., m, sub, dk) -> block diagonal (.., C, C)
            # products and sums spelled out elementwise: a matmul at the
            # TPU's default precision would round them to bfloat16
            blocks = jnp.sum(rows[..., :, None, :] * k_sub[..., None, :, :] * pair,
                             axis=-1)                       # (.., m, t, i)
            same = jnp.eye(m, dtype=bool)[:, None, :, None]     # (a, 1, b, 1)
            return jnp.where(same, blocks[..., :, :, None, :], 0.0).reshape(
                b, h, n, c, c)

        q_sub = q.reshape(rel.shape)
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        A = jnp.where(strict, off(k_rel) + diag(k_sub), 0.0) * beta[..., None]
        Bm = off(q_rel) + diag(q_sub)                       # i <= t
        # W = (I + A)^-1 beta (V - (K e^G) S0) = U - Wk S0: one solve
        rhs = jnp.concatenate([v, k * jnp.exp(G)], axis=-1) * beta[..., None]
        solved = lax.linalg.triangular_solve(
            A + jnp.eye(c, dtype=A.dtype), rhs, left_side=True, lower=True,
            unit_diagonal=True)
        U, Wk = solved[..., :dv], solved[..., dv:]
        q_in = q * jnp.exp(G)                               # against S0
        last = G[..., -1:, :]                               # the chunk's whole sum
        k_out = k * jnp.exp(last - G)                       # up to the chunk's end

        def step(S, x):
            U, Wk, Bm, q_in, k_out, last = x
            W = U - jnp.einsum("bhtd,bhde->bhte", Wk, S, precision=_HI)
            o = (jnp.einsum("bhtd,bhde->bhte", q_in, S, precision=_HI)
                 + jnp.einsum("bhti,bhie->bhte", Bm, W, precision=_HI))
            S = (S * jnp.exp(last[..., 0, :])[..., None]
                 + jnp.einsum("bhtd,bhte->bhde", k_out, W, precision=_HI))
            return S, o

        lead = lambda x: jnp.moveaxis(x, 2, 0)              # noqa: E731
        state, o = lax.scan(step, state.astype(jnp.float32),
                            tuple(lead(x) for x in (U, Wk, Bm, q_in, k_out, last)))
        o = jnp.moveaxis(o, 0, 2) / jnp.sqrt(jnp.float32(dk))   # (B, H, n, C, dv)
        o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, dv)[:, :t]
    return o, state


def short_conv(x, weight, tail=None, length=None):
    """Depthwise causal convolution over time, a weight a channel and tap,
    no bias: ``x`` (B, T, C), ``weight`` (K, C): ``y_t = sum_j weight[j] *
    x_{t - (K-1) + j}``.  ``tail`` (B, K-1, C) holds the inputs before
    position 0 (``None``: zeros).  -> (y (B, T, C) float32, the tail a later
    call starts from: the last K-1 inputs of the first ``length`` positions,
    ``length`` (B,) int or ``None`` for all T -- bucket padding past a row's
    true end does not reach it)."""
    with jax.named_scope("kda/conv"):
        b, t, ch = x.shape
        taps = weight.shape[0]
        if tail is None:
            tail = jnp.zeros((b, taps - 1, ch), x.dtype)
        xs = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        w = weight.astype(jnp.float32)
        y = sum(xs[:, j:j + t].astype(jnp.float32) * w[j] for j in range(taps))
        if length is None:
            return y, xs[:, t:]
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
        new_tail = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
            row, n, taps - 1, axis=0))(xs, length)
        return y, new_tail


def short_conv_step(x, weight, tail):
    """:func:`short_conv` for one new position: ``x`` (..., C), ``tail``
    (..., K-1, C) -> (y (..., C) float32, tail')."""
    with jax.named_scope("kda/conv"):
        xs = jnp.concatenate([tail.astype(x.dtype), x[..., None, :]], axis=-2)
        y = jnp.sum(xs.astype(jnp.float32) * weight.astype(jnp.float32), axis=-2)
        return y, xs[..., 1:, :]


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_scan(q, k, v, g, beta, state: Optional[jax.Array] = None, valid=None):
    """:func:`kda_step` folded over the positions of whole sequences (the
    shapes of :func:`kda_chunked`): the literal recurrence, for tests and
    for anything that wants it without chunks."""
    b, t, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    g, beta = _masked(g, beta, valid)

    def step(S, x):
        o, S = kda_step(*x, S)
        return S, o

    state, o = lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state
