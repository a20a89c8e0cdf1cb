"""Expert parallelism: a mixture-of-experts layer sharded over the
``expert`` mesh axis.

Capability extension beyond the reference (SURVEY.md §5.8; its closest
ancestor is ``MixtureTable``, which mixes full expert outputs on one
node).  TPU-first design, top-1 (switch) routing with a load-balancing
auxiliary loss, two dispatch modes:

- ``capacity_factor=None`` — dense dispatch: every expert sees every
  token, masked.  Exact (no token drops) but expert compute scales with
  n_experts x tokens; kept as the correctness oracle and for tiny T.
- ``capacity_factor=c`` — Switch/GShard capacity dispatch: each expert
  processes at most ``C = ceil(c * T / n_experts)`` tokens via a static
  (T, E, C) one-hot dispatch tensor (einsum dispatch keeps shapes static
  — no ragged gather/scatter), tokens over capacity are dropped (their
  output is zero, the standard Switch behavior).  Per-token expert-FFN
  FLOPs are then independent of the expert count — the scaling story
  expert parallelism exists for.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.mesh import EXPERT_AXIS


def init_moe_params(rng, n_experts: int, d_model: int, d_hidden: int):
    """Gate + per-expert 2-layer MLPs, stacked on a leading expert dim."""
    kg, k1, k2 = jax.random.split(rng, 3)
    scale = 1.0 / jnp.sqrt(d_model)
    return {
        "gate": jax.random.uniform(kg, (d_model, n_experts), jnp.float32,
                                   -scale, scale),
        "w1": jax.random.uniform(k1, (n_experts, d_model, d_hidden),
                                 jnp.float32, -scale, scale),
        "w2": jax.random.uniform(k2, (n_experts, d_hidden, d_model),
                                 jnp.float32, -scale, scale),
    }


# shared routing/dispatch core — ONE definition of the top-1 routing,
# the capacity position trick, the expert FFN (gelu, matching the dense
# transformer block so --moeExperts A/Bs routing and nothing else), and
# the balance loss; moe_apply_local and switch_mlp are thin shells over
# these with/without the expert-slice + psum machinery.

def _top1_route(gate, x2):
    """-> (probs f32, onehot top-1 mask, gate value per token)."""
    logits = x2 @ gate
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(top, gate.shape[1], dtype=x2.dtype)
    gate_val = jnp.sum(probs.astype(x2.dtype) * onehot, axis=-1)
    return probs, onehot, gate_val


def _capacity_positions(onehot, cap):
    """(T, C) one-hot of each token's slot within its expert's queue;
    over-capacity tokens get a zero row (the Switch drop).  Integer
    cumsum: a bf16 cumsum stops counting exactly at 256 and would
    silently collide capacity slots."""
    oh_i = onehot.astype(jnp.int32)
    pos = jnp.sum(jnp.cumsum(oh_i, axis=0) * oh_i, axis=-1) - 1
    return jax.nn.one_hot(pos, cap, dtype=onehot.dtype)


def _expert_ffn(w1, w2, x):
    h = jax.nn.gelu(jnp.einsum("e...d,edh->e...h", x, w1),
                    approximate=True)
    return jnp.einsum("e...h,ehd->e...d", h, w2)


def _balance_loss(onehot, probs, n_total, data_axis=None):
    """Switch load-balancing loss n * sum_e f_e * P_e.  With
    ``data_axis``, f_e and P_e average over token shards FIRST (averaging
    the per-shard products would add a cross-shard covariance term and
    penalize shard-skewed-but-globally-balanced routing)."""
    frac = jnp.mean(onehot.astype(jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    if data_axis is not None:
        frac = lax.pmean(frac, data_axis)
        mean_p = lax.pmean(mean_p, data_axis)
    return n_total * jnp.sum(frac * mean_p)


def moe_apply_local(params, x, *, axis: str = EXPERT_AXIS,
                    data_axis: Optional[str] = None,
                    capacity_factor: Optional[float] = None):
    """Per-device body (inside shard_map over ``axis``).  ``params['w1'/
    'w2']`` hold the LOCAL expert slice (E_local, ...); ``x`` (T, D) is
    replicated over the axis.  Returns (y (T, D), aux_loss)."""
    e_local = params["w1"].shape[0]
    my_idx = lax.axis_index(axis)
    n_total = params["gate"].shape[1]

    probs, onehot, gate_val = _top1_route(params["gate"], x)
    lo = my_idx * e_local
    local_mask = lax.dynamic_slice_in_dim(onehot, lo, e_local, axis=1)

    if capacity_factor is None:
        # dense dispatch to the local slice only (exact; oracle path)
        dispatched = jnp.einsum("te,td->etd", local_mask, x)  # (E_l, T, D)
        out = _expert_ffn(params["w1"], params["w2"], dispatched)
        y_local = jnp.einsum("etd,te->td", out, local_mask)
        y = lax.psum(y_local, axis) * gate_val[:, None]
    else:
        # Switch capacity dispatch: expert e takes its first C routed
        # tokens; the (T, E, C) one-hot keeps every shape static
        t_tokens = x.shape[0]
        cap = max(1, int(math.ceil(capacity_factor * t_tokens / n_total)))
        pos_oh = _capacity_positions(onehot, cap)
        dispatch = local_mask[:, :, None] * pos_oh[:, None, :]  # (T,E_l,C)
        expert_in = jnp.einsum("td,tec->ecd", x, dispatch)      # (E_l,C,D)
        out = _expert_ffn(params["w1"], params["w2"], expert_in)
        combine = dispatch * gate_val[:, None, None]
        y = lax.psum(jnp.einsum("ecd,tec->td", out, combine), axis)

    aux = _balance_loss(onehot, probs, n_total, data_axis)
    return y, aux


def switch_mlp(params, x, capacity_factor: Optional[float] = None,
               balance_axis: Optional[str] = None):
    """Single-device switch MoE over tokens x (..., T, D) — the same
    routing/dispatch core as ``moe_apply_local`` with all experts
    resident (no mesh).  This is the block ``TransformerLM`` uses for
    ``moe_experts > 0``; the mesh version shards the same parameter
    layout over the ``expert`` axis.  ``balance_axis``: when the call
    runs inside shard_map with tokens sharded over that axis (data
    parallelism), the balance loss uses globally averaged f_e/P_e so it
    stays the unbiased Switch objective.  Returns (y, aux_loss)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    n_experts = params["gate"].shape[1]

    probs, onehot, gate_val = _top1_route(params["gate"], x2)

    if capacity_factor is None:
        dispatched = jnp.einsum("te,td->etd", onehot, x2)
        out = _expert_ffn(params["w1"], params["w2"], dispatched)
        y = jnp.einsum("etd,te->td", out, onehot) * gate_val[:, None]
    else:
        t_tokens = x2.shape[0]
        cap = max(1, int(math.ceil(capacity_factor * t_tokens / n_experts)))
        pos_oh = _capacity_positions(onehot, cap)
        dispatch = onehot[:, :, None] * pos_oh[:, None, :]     # (T, E, C)
        expert_in = jnp.einsum("td,tec->ecd", x2, dispatch)
        out = _expert_ffn(params["w1"], params["w2"], expert_in)
        combine = dispatch * gate_val[:, None, None]
        y = jnp.einsum("ecd,tec->td", out, combine)

    aux = _balance_loss(onehot, probs, n_experts, balance_axis)
    return y.reshape(shape), aux


def moe_apply(params, x, mesh: Mesh, *, axis: str = EXPERT_AXIS,
              data_axis: Optional[str] = None,
              capacity_factor: Optional[float] = None):
    """Global-view MoE over tokens ``x`` (T, D) (or (B, T, D) — flattened
    internally).  Experts shard over ``axis``; pass ``data_axis`` to keep
    the token batch sharded over it on a 2-D mesh.  ``capacity_factor``
    switches to capacity-bounded dispatch (see module docstring); the
    capacity applies per token shard.  Returns (y, aux)."""
    shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, shape[-1])
    xspec = P(data_axis, None) if data_axis else P(None, None)
    pspec = {"gate": P(None, None), "w1": P(axis, None, None),
             "w2": P(axis, None, None)}
    fn = shard_map(partial(moe_apply_local, axis=axis, data_axis=data_axis,
                           capacity_factor=capacity_factor),
                   mesh=mesh, in_specs=(pspec, xspec),
                   out_specs=(xspec, P()))
    y, aux = fn(params, x)
    if len(shape) == 3:
        y = y.reshape(shape)
    return y, jnp.mean(aux)
