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

Serving's routed layer is the second half of this file
(:func:`routed_experts`, :class:`MoESpec`): top-k dropless routing over
all experts, the assignments that land on the experts HELD here sorted by
expert and run as one grouped matmul, a shared expert beside them.  A
holder is told which experts it has and computes their part of the
result: the same function is one chip's share of an expert-parallel
deployment and, under the ``expert`` mesh axis, the per-device body
before a ``psum``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.ops import _pallas
from bigdl_tpu.ops.grouped_matmul import grouped_matmul, row_tiles
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


# ---------------------------------------------------------------------- #
# top-k dropless routing over a SHARE of the experts (serving MoE)
# ---------------------------------------------------------------------- #

class MoESpec(NamedTuple):
    """A routed expert layer as a model states it: ``n_experts`` routed
    over (the router's width), ``top_k`` a token, SwiGLU experts of
    ``width``, an always-on shared expert of ``shared_width`` (0: none),
    the chosen weights renormalised (``norm_topk``) and multiplied by
    ``routed_scale``.  ``held = (first, count)`` names the experts THIS
    holder has (expert parallelism's share; ``None``: all of them): the
    router still scores all ``n_experts`` and the layer computes the part
    of the result its own experts give.  ``score`` is how a router logit
    becomes a weight: ``"softmax"`` over all experts, or ``"sigmoid"`` an
    expert, chosen by the score plus a ``select_bias`` parameter
    (n_experts,) beside the router and weighed by the score alone.
    ``n_group`` > 1 is GROUP-LIMITED selection (a sigmoid router's): the
    experts form ``n_group`` groups of consecutive ones, a group scores the
    sum of its two largest ``score + select_bias``, and the ``top_k`` are
    chosen inside the ``topk_group`` best groups alone -- with a group a
    chip, a token's experts lie on at most ``topk_group`` chips.  1 and 1:
    no groups."""
    n_experts: int
    top_k: int
    width: int
    shared_width: int = 0
    routed_scale: float = 1.0
    norm_topk: bool = True
    held: Optional[Tuple[int, int]] = None
    score: str = "softmax"
    n_group: int = 1
    topk_group: int = 1

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held is None else int(self.held[1])

    @property
    def n_counts(self) -> int:
        """How many integers a routed layer counts (:func:`routed_experts`):
        the groups hit ride behind the two where the router has groups, the
        row tiles visited last."""
        return 4 if self.n_group > 1 else 3


def init_routed_params(rng, spec: MoESpec, d_model: int):
    """Router over all experts, the held experts' SwiGLU matrices stacked
    on a leading expert axis, and the shared expert's."""
    ks = jax.random.split(rng, 7)
    s_in, s_mid = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(spec.width)
    e, f = spec.n_held, spec.width
    p = {"router": jax.random.normal(ks[0], (d_model, spec.n_experts)) * s_in,
         "w_gate": jax.random.normal(ks[1], (e, d_model, f)) * s_in,
         "w_up": jax.random.normal(ks[2], (e, d_model, f)) * s_in,
         "w_down": jax.random.normal(ks[3], (e, f, d_model)) * s_mid}
    if spec.score == "sigmoid":
        p["select_bias"] = jnp.zeros((spec.n_experts,), jnp.float32)
    if spec.shared_width:
        g = spec.shared_width
        p["shared"] = {
            "w_gate": jax.random.normal(ks[4], (d_model, g)) * s_in,
            "w_up": jax.random.normal(ks[5], (d_model, g)) * s_in,
            "w_down": jax.random.normal(ks[6], (g, d_model))
            / math.sqrt(g)}
    return p


def route_top_k(router, x2, spec: MoESpec, select_bias=None):
    """Scores over ALL experts in f32 (``spec.score``), the ``top_k``
    largest, their weights renormalised and scaled: -> (idx (T, k) int32,
    w (T, k) f32).  A sigmoid router selects on ``score + select_bias``
    (inside its best groups, where it has groups: :class:`MoESpec`) and
    weighs by the unbiased score."""
    logits = jnp.dot(x2, router.astype(x2.dtype),
                     preferred_element_type=jnp.float32)
    if spec.n_group > 1 and spec.score != "sigmoid":
        raise ValueError("group-limited selection (MoESpec.n_group > 1) is a "
                         "sigmoid router's")
    if spec.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + select_bias.astype(jnp.float32)
        if spec.n_group > 1:
            with jax.named_scope("moe/group_select"):
                by_group = biased.reshape(biased.shape[0], spec.n_group, -1)
                best = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
                _, kept = lax.top_k(best, spec.topk_group)  # (T, topk_group)
                stays = jnp.any(kept[:, :, None] == jnp.arange(spec.n_group),
                                axis=1)                      # (T, n_group)
                biased = jnp.where(stays[:, :, None], by_group,
                                   -jnp.inf).reshape(biased.shape)
        _, idx = lax.top_k(biased, spec.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    elif spec.score == "softmax":
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), spec.top_k)
    else:
        raise ValueError(f"MoESpec.score must be 'softmax' or 'sigmoid', "
                         f"got {spec.score!r}")
    if spec.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * spec.routed_scale


def swiglu(p, x):
    """``(silu(x W_gate) * (x W_up)) W_down``: the dense gated MLP, the
    shared expert, and one expert's function."""
    from bigdl_tpu.quant.kernels import qmatmul
    return qmatmul(jax.nn.silu(qmatmul(x, p["w_gate"]))
                   * qmatmul(x, p["w_up"]), p["w_down"])


#: the rows (or their products) a call of ``ops.grouped_matmul`` keeps in VMEM
KERNEL_ROWS_BYTES = 8 << 20


def expert_matmul_path(rows: int, d_model: int, width: int, dtype) -> str:
    """Which grouped matmul a routed layer takes, from what is known when a
    step program is traced: the platform and the call's static shapes.
    ``"grouped_kernel"`` (``ops.grouped_matmul``, a weight-streaming Pallas
    kernel) on a TPU where the rows -- and their products, the wider of the
    two -- lie in VMEM whole (``KERNEL_ROWS_BYTES``) while the hit experts'
    matrices stream past them once: a decode, verify or self-drafting round,
    a bucket of a few hundred tokens.  ``"ragged_dot"`` (``lax.ragged_dot``)
    for everything else: the CPU, and the prefills whose rows would have to
    stream too.  The limit is the kernel's own, not a crossover: alone on the
    chip it reads faster than ``ragged_dot`` at every shape tried, 256 rows
    to 4,096 (1.02 to 2.7 times: PERF.md, PR 41)."""
    if _pallas.use_interpret():
        return "ragged_dot"
    rows_bytes = rows * max(d_model, width) * jnp.dtype(dtype).itemsize
    return "grouped_kernel" if rows_bytes <= KERNEL_ROWS_BYTES else "ragged_dot"


def grouped_swiglu(params, xs, sizes):
    """SwiGLU over rows SORTED by expert: rows ``[sum(sizes[:e]),
    sum(sizes[:e+1]))`` go through expert ``e``'s matrices, one grouped
    matmul a product with work proportional to the rows
    (:func:`expert_matmul_path`: ``lax.ragged_dot``, or on a TPU's
    decode-sized calls ``ops.grouped_matmul``, gate and up in one call).
    Rows past ``sum(sizes)`` come back unspecified.  -> (rows, the row
    tiles the kernel visited: 0 on the other path)."""
    _, d, f = params["w_gate"].shape
    if expert_matmul_path(xs.shape[0], d, f, xs.dtype) == "grouped_kernel":
        hidden = grouped_matmul(xs, (params["w_gate"], params["w_up"]), sizes)
        return (grouped_matmul(hidden, params["w_down"], sizes),
                row_tiles(sizes, xs.shape[0], xs.dtype))

    def dot(a, w):
        return lax.ragged_dot(a, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(xs.dtype)

    hidden = jax.nn.silu(dot(xs, params["w_gate"])) * dot(xs, params["w_up"])
    return dot(hidden, params["w_down"]), jnp.zeros((), jnp.int32)


def routed_experts(params, x, spec: MoESpec, *, token_mask=None,
                   axis: Optional[str] = None):
    """Top-k dropless routed experts over tokens ``x`` (..., D): route
    over all ``spec.n_experts``, keep the (token, expert) assignments
    whose expert is HELD here, sort them by expert, one grouped matmul
    (:func:`grouped_swiglu`), and add each result back to its token under
    its routing weight.  No capacity and no dropped token; the work is
    proportional to the assignments that land here.  What the absent
    experts would add is left out: the sum over every holder's result is
    the uncut layer's.  Under ``axis`` (inside ``shard_map`` over the
    expert mesh axis, ``params`` holding the local slice) the holder is
    ``axis_index`` and a ``psum`` completes the sum; on one chip the same
    body runs without that exchange.  ``token_mask`` (...,) bool leaves
    tokens out of the routing (a decode step's idle slots).

    Returns ``(y, counts)``: ``counts`` int32 (``spec.n_counts``,) =
    assignments that landed here, distinct held experts hit; behind them,
    where the router has groups, the groups (of ALL the experts) in which
    the routed tokens have a chosen expert, summed over the tokens; LAST the
    row tiles the grouped matmul's kernel visited (:func:`grouped_swiglu`)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    t, k = x2.shape[0], spec.top_k
    count = params["w_gate"].shape[0]
    if axis is not None:
        first = lax.axis_index(axis) * count
    else:
        first = 0 if spec.held is None else spec.held[0]
    with jax.named_scope("moe/route"):
        idx, w = route_top_k(params["router"], x2, spec,
                             params.get("select_bias"))
        local = idx - first
        here = (local >= 0) & (local < count)
        if token_mask is not None:
            here = here & token_mask.reshape(-1)[:, None]
        # assignments sorted by held expert; the rest sort behind them
        key = jnp.where(here, local, count).reshape(-1)        # (T*k,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    with jax.named_scope("moe/experts"):
        out, tiles = grouped_swiglu(params, x2[order // k], sizes)
        # back to (token, choice) order; an assignment that is not here
        # carries whatever the grouped matmul left in its row: weight 0
        out = out[back].reshape(t, k, -1).astype(jnp.float32)
        y = jnp.sum(jnp.where(here[..., None], out * w[..., None], 0.0),
                    axis=1).astype(x.dtype)
    if axis is not None:
        y = lax.psum(y, axis)
    counts = [jnp.sum(here), jnp.sum(sizes > 0)]
    if spec.n_group > 1:
        group = idx // (spec.n_experts // spec.n_group)        # (T, k)
        hit = jnp.any(group[:, :, None] == jnp.arange(spec.n_group), axis=1)
        if token_mask is not None:
            hit = hit & token_mask.reshape(-1)[:, None]
        counts.append(jnp.sum(hit))
    counts = jnp.stack(counts + [tiles]).astype(jnp.int32)
    return y.reshape(shape), counts


def routed_mlp(params, x, spec: MoESpec, *, token_mask=None,
               axis: Optional[str] = None):
    """The sparse block's feed-forward half: the held experts' part of the
    routed sum plus the shared expert (which every holder computes alike
    and which counts once).  -> (y, counts)."""
    y, counts = routed_experts(params, x, spec, token_mask=token_mask,
                               axis=axis)
    if spec.shared_width:
        with jax.named_scope("moe/shared"):
            y = y + swiglu(params["shared"], x)
    return y, counts
