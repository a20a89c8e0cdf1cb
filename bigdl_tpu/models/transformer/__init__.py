"""Decoder-only transformer language model (capability-gap fill: the
reference's language-model family tops out at Recurrent/LSTM,
models/rnn/SimpleRNN.scala:22 — this is the long-context successor the
survey's §5.7 gap-fill analysis calls for, built on the same training
surfaces: 1-based LookupTable ids in, (B, T, V) log-probs out, trained
with TimeDistributedCriterion(ClassNLLCriterion) exactly like the RNN
family so every Optimizer/Validator path is shared).

TPU-first structure instead of a stack of OO layers:

- all transformer blocks share ONE traced body via ``lax.scan`` over
  layer-stacked parameters — compile time is O(1) in depth, and XLA still
  pipelines the per-layer matmuls onto the MXU back-to-back;
- the attention core is the Pallas flash kernel on TPU
  (``bigdl_tpu.ops.flash_attention``; interpret mode elsewhere), so the
  (T, T) score matrix never exists in HBM in forward OR backward;
- optional ``remat`` wraps the block in ``jax.checkpoint`` — activation
  memory O(sqrt-ish) for long sequences, the standard bandwidth/FLOPs
  trade on HBM-bound chips;
- pre-LayerNorm residual wiring, learned positional embedding, weight-tied
  LM head (embedding.T) by default.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Module


def apply_rope(x, positions, base: float = 10000.0):
    """Rotary position embedding (rotate-half convention): x (..., T, D)
    with D even, positions (T,) absolute indices.  Attention scores then
    depend only on RELATIVE position — no learned table, graceful
    behavior past training lengths, and exact compatibility with KV
    caches (keys are rotated once, at their own position)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.asarray(base, jnp.float32) ** (
        -jnp.arange(0, half, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[..., :, None] * freqs  # (T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                            (x2 * cos + x1 * sin).astype(x.dtype)], -1)


class RopeSpec(NamedTuple):
    """One layer kind's rotary embedding: ``theta``, how many leading dims
    of each head rotate (``rotary_dim``; ``None``: the whole head, the
    rest pass through), and YaRN's ``(factor, original_max_positions,
    beta_fast, beta_slow)`` with the ``attention_factor`` that multiplies
    cos and sin (Peng et al. 2023; the frequencies are computed over the
    ROTATED dims, as the published configs of partial-rotary models do)."""
    theta: float = 10000.0
    rotary_dim: Optional[int] = None
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: float = 1.0

    def inv_freq(self, head_dim: int) -> np.ndarray:
        dim = int(self.rotary_dim or head_dim)
        pos_freqs = float(self.theta) ** (np.arange(0, dim, 2,
                                                    dtype=np.float64) / dim)
        if self.yarn is None:
            return (1.0 / pos_freqs).astype(np.float32)
        factor, original, beta_fast, beta_slow = self.yarn

        def correction_dim(rotations):
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        # ramp 0: the high frequencies keep their own (extrapolation);
        # ramp 1: the low ones are divided by the factor (interpolation)
        return ((1.0 / pos_freqs) * (1.0 - ramp)
                + (1.0 / (factor * pos_freqs)) * ramp).astype(np.float32)


def apply_rotary(x, positions, inv_freq, scale: float = 1.0):
    """Rotate-half rotary over the first ``2 * len(inv_freq)`` dims of
    each head, cos and sin multiplied by ``scale``; the other dims pass
    through.  ``positions`` broadcasts as in :func:`apply_rope`."""
    half = int(inv_freq.shape[0])
    ang = positions.astype(jnp.float32)[..., :, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    parts = [(x1 * cos - x2 * sin).astype(x.dtype),
             (x2 * cos + x1 * sin).astype(x.dtype)]
    if 2 * half < x.shape[-1]:
        parts.append(x[..., 2 * half:])
    return jnp.concatenate(parts, -1)


class LayerSpec(NamedTuple):
    """What one layer of a plan differs by: its query heads, its window
    (``None``: every earlier key; ``w``: key j for query i iff
    ``i - j < w``), its rotary embedding (``None``: the model's
    ``pos_encoding``), its feed-forward half (``"dense"`` or ``"moe"``,
    the model's ``moe`` spec) and its token mixer: ``"attention"`` (softmax
    over cached keys and values), ``"kda"`` (gated delta-rule linear
    attention, :mod:`bigdl_tpu.nn.kda`: ``n_head`` heads of ``head_dim``
    keys and values, NO keys and values kept, a state a head instead) or
    ``"mla"`` (latent attention of the model's :class:`MLASpec`: softmax
    over ONE cached row a position, nothing a head; ``rope`` rotates its
    ``MLASpec.rope`` lanes).  An ``"attention"`` layer may have its OWN count
    of K/V heads (``n_kv_head``; ``None``: the model's) and a learned SINK
    (``sink``): one float32 logit a query head that joins the softmax's
    denominator and owns no value (``den += exp(sink_h - m)``)."""
    n_head: int
    window: Optional[int] = None
    rope: Optional[RopeSpec] = None
    mlp: str = "dense"
    mixer: str = "attention"
    n_kv_head: Optional[int] = None
    sink: bool = False


class CacheClass(NamedTuple):
    """One KIND of softmax layer as a paged pool sees it: the plan's
    ``"attention"`` layers that cache the same row -- ``n_kv`` K/V heads of
    ``k_dim`` key and ``v_dim`` value lanes -- for the same lifetime (a
    ``window``: what lies behind it is never read again; ``None``: the whole
    context).  ``layers``: the (absolute) layers of the class, in plan order;
    a layer's place among them is its layer of the class's arenas."""
    n_kv: int
    k_dim: int
    v_dim: int
    window: Optional[int]
    layers: tuple


class MLASpec(NamedTuple):
    """Latent attention's shape, stated once (DeepSeek-V2's multi-head
    latent attention).  A position's keys and values are up-projections of
    ONE latent ``c`` of ``kv_rank`` lanes (RMS-normed), beside ``rope``
    rotated lanes shared by every head; the query is one matrix a layer
    (``q_rank`` ``None`` or 0) or, COMPRESSED, a down-projection to
    ``q_rank`` lanes, an RMSNorm and an up-projection (``q = rmsnorm(a W_qa)
    W_qb``)::

        q_h = [q_n (nope) ; q_r (rope)]        [c' ; k_r'] = a W_dkv
        c = rmsnorm(c')     q_r, k_r = rotary(q_r', k_r')
        [k_n,h ; v_h] = c W_ukv,h              (nope + v lanes a head)
        s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(nope + rope)

    What a position caches is the ``row`` ``[c ; k_r]``.  Two forms of the
    one function: EXPANDED (keys and values up-projected: whole sequences)
    and ABSORBED (``W_uk`` folded into the query, ``W_uv`` applied after the
    softmax: the cached step, whose values are the row's first ``kv_rank``
    lanes)."""
    kv_rank: int
    nope: int
    rope: int
    v: int
    q_rank: Optional[int] = None

    @property
    def row(self) -> int:
        """Lanes of the one row a position caches."""
        return self.kv_rank + self.rope

    @property
    def score_dim(self) -> int:
        return self.nope + self.rope


class KDASpec(NamedTuple):
    """The variants of a ``"kda"`` layer a model states.  The decay's log
    ``g`` from its logit ``f = (a Wf) + dt_bias`` and rate ``r = exp(A_log)``
    a head: ``"softplus"``: ``-r * softplus(f)`` in (-inf, 0);
    ``"bounded"``: ``lower_bound * sigmoid(r * f)`` in (lower_bound, 0).
    ``full_rank``: the decay's and the output gate's projections are one
    hidden x (H * D) matrix each (``wf``, ``wg``), else a low-rank pair
    through ``head_dim`` (``wf1`` ``wf2``, ``wg1`` ``wg2``).  ``beta_scale``:
    beta = ``beta_scale * sigmoid(.)``, in (0, 2) where the transition may
    have a negative eigenvalue, in (0, 1) where not."""
    gate: str = "softplus"
    lower_bound: float = -5.0
    full_rank: bool = False
    beta_scale: float = 2.0


def window_mask(q_pos, k_pos, window: Optional[int]):
    """Causal (and windowed) visibility of key positions ``k_pos`` (..., Tk)
    to query positions ``q_pos`` (..., Tq): -> (..., Tq, Tk) bool."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    return (d >= 0) if window is None else (d >= 0) & (d < window)


class TransformerLM(Module):
    """Causal transformer LM over 1-based token ids.

    Input: (B, T) ids in [1, vocab] (float or int — the data pipeline's
    ``LabeledSentenceToSample(one_hot=False)`` emits 1-based floats for
    LookupTable parity).  Output: (B, T, vocab) log-probabilities.
    """

    # class-level default: checkpoint restore builds instances via
    # __new__ + saved __dict__ (file_io.build_module), so a model saved
    # before this attribute existed must still forward cleanly
    doc_start_id: Optional[int] = None
    # the same for the block parameters a layer plan brought: an older
    # checkpoint is GPT-2's block, a plan of one uniform group
    layer_plan = None
    n_kv_head = None
    norm, norm_eps, mlp_act, bias, attn_gate, moe = (
        "layernorm", 1e-5, "gelu", True, False, None)
    kda_conv = 4
    kda, mla = KDASpec(), None
    mtp = None
    v_head_dim, value_scale = None, 1.0

    def __init__(self, vocab_size: int, hidden_size: int = 128,
                 n_head: int = 4, n_layers: int = 2,
                 ffn_size: Optional[int] = None, max_len: int = 512,
                 dropout: float = 0.0, tie_embeddings: bool = True,
                 remat: bool = False, attention_impl: str = "auto",
                 block_size: Optional[int] = None,
                 pos_encoding: str = "learned",
                 rope_base: float = 10000.0,
                 moe_experts: int = 0,
                 moe_capacity_factor: Optional[float] = 1.25,
                 moe_aux_weight: float = 0.01,
                 doc_start_id: Optional[int] = None,
                 n_kv_head: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 norm: str = "layernorm", norm_eps: float = 1e-5,
                 mlp_act: str = "gelu", bias: bool = True,
                 attn_gate=False, moe=None,
                 layer_plan: Optional[Sequence] = None,
                 kda_conv: int = 4, kda: Optional[KDASpec] = None,
                 mla: Optional[MLASpec] = None,
                 mtp: Optional[LayerSpec] = None,
                 v_head_dim: Optional[int] = None,
                 value_scale: float = 1.0):
        super().__init__()
        assert head_dim is not None or hidden_size % n_head == 0
        if norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', "
                             f"got {norm!r}")
        if mlp_act not in ("gelu", "swiglu"):
            raise ValueError(f"mlp_act must be 'gelu' or 'swiglu', "
                             f"got {mlp_act!r}")
        if pos_encoding not in ("learned", "rope", "none"):
            raise ValueError(f"pos_encoding must be 'learned', 'rope' or "
                             f"'none', got {pos_encoding!r}")
        if attn_gate is True:       # the gate as it was first written
            attn_gate = "per-head"
        if attn_gate not in (False, "per-head", "elementwise"):
            raise ValueError(f"attn_gate must be False, 'per-head' or "
                             f"'elementwise', got {attn_gate!r}")
        if pos_encoding == "rope" and (hidden_size // n_head) % 2 != 0:
            raise ValueError("rope needs an even head_dim")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.n_layers = n_layers
        self.ffn_size = ffn_size or 4 * hidden_size
        self.max_len = max_len
        self.dropout = dropout
        self.tie_embeddings = tie_embeddings
        self.remat = remat
        self.pos_encoding = pos_encoding
        self.rope_base = rope_base
        # moe_experts > 0 swaps every block's dense MLP for a top-1
        # switch MoE (bigdl_tpu.parallel.expert.switch_mlp); the
        # load-balancing auxiliary loss reaches the optimizers through
        # the reserved "aux_loss" buffers key, pre-scaled by
        # moe_aux_weight
        self.moe_experts = int(moe_experts)
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_weight = moe_aux_weight
        # set to the data-parallel mesh axis name when the forward runs
        # inside shard_map with tokens sharded over it: the balance loss
        # then averages f_e/P_e globally (DistriOptimizer sets this
        # automatically; see expert._balance_loss for why it matters)
        self.moe_balance_axis: Optional[str] = None
        # packed-document isolation: when set (1-based vocab id of the
        # document-start marker, e.g. the Dictionary index of
        # text.SENTENCE_START + 1), segment ids are derived from the
        # input ids themselves (cumsum of marker positions) and
        # attention is masked across document boundaries — inside the
        # flash tiles on TPU, via an explicit mask on the XLA path.  No
        # pipeline plumbing: DocumentPacker windows already carry the
        # markers.  Positions stay window-absolute (standard packing).
        self.doc_start_id = doc_start_id
        # attention plumbing (projections + kernel choice) is shared with
        # the standalone nn.MultiHeadAttention so there is one hot path
        self._mha = nn.MultiHeadAttention(
            hidden_size, n_head, head_dim=head_dim, causal=True,
            with_bias=bias, attention_impl=attention_impl,
            block_size=block_size)
        # -- the block's parameters beyond GPT-2's (all default to it) -- #
        # RMSNorm or LayerNorm; a gated (SwiGLU) or a GELU MLP; biases or
        # none; K/V heads shared by groups of query heads; a sigmoid gate
        # on the attention output, a scalar a head or elementwise; a routed
        # expert layer (parallel.expert.MoESpec) on the plan's "moe" layers;
        # the taps of a "kda" layer's depthwise convolution and its variants;
        # the shape of an "mla" layer
        self.n_kv_head = int(n_kv_head or n_head)
        self.norm, self.norm_eps = norm, float(norm_eps)
        self.mlp_act, self.bias, self.attn_gate = mlp_act, bool(bias), attn_gate
        self.moe = moe
        self.kda_conv = int(kda_conv)
        self.kda = KDASpec(*kda) if kda is not None else KDASpec()
        if self.kda.gate not in ("softplus", "bounded"):
            raise ValueError(f"KDASpec.gate must be 'softplus' or 'bounded', "
                             f"got {self.kda.gate!r}")
        self.mla = MLASpec(*mla) if mla is not None else None
        # a softmax layer's values may be narrower than its keys (``None``:
        # the head's own width) and scaled before they are cached and read
        self.v_head_dim = int(v_head_dim) if v_head_dim else None
        self.value_scale = float(value_scale)
        # the LAYER PLAN: a list of groups ``(repeat, period)``, a period a
        # tuple of LayerSpec.  A group is ``repeat`` copies of its period
        # stacked on a leading axis and scanned; the body runs the period's
        # layers in turn, each with its own shapes (a period of one layer
        # is a stack of identical layers).  ``None`` is GPT-2: one group of
        # ``n_layers`` identical layers, parameters under "blocks" as ever.
        if layer_plan is not None:
            layer_plan = tuple((int(r), tuple(LayerSpec(*s) for s in period))
                               for r, period in layer_plan)
            depth = sum(r * len(period) for r, period in layer_plan)
            if depth != n_layers:
                raise ValueError(f"layer_plan holds {depth} layers, "
                                 f"n_layers is {n_layers}")
            for _, period in layer_plan:
                for spec in period:
                    if spec.mixer not in ("attention", "kda", "mla"):
                        raise ValueError(f"a layer's mixer is 'attention', "
                                         f"'kda' or 'mla', got {spec.mixer!r}")
                    if spec.mixer == "mla" and (self.mla is None or self.bias
                                                or spec.window):
                        raise ValueError("an 'mla' layer needs mla=MLASpec, "
                                         "has no biases and no window")
                    if spec.mixer == "attention" and (
                            spec.n_head % (spec.n_kv_head or self.n_kv_head)):
                        raise ValueError(
                            f"{spec.n_head} query heads do not divide over "
                            f"{spec.n_kv_head or self.n_kv_head} K/V heads")
                    if spec.mixer != "attention" and (spec.n_kv_head
                                                      or spec.sink):
                        raise ValueError("K/V heads of its own and a sink "
                                         "are an 'attention' layer's")
                    if spec.mlp == "moe" and moe is None:
                        raise ValueError("a 'moe' layer needs moe=MoESpec")
        elif (self.n_kv_head != n_head or attn_gate or moe is not None
              or mla is not None or v_head_dim or value_scale != 1.0):
            raise ValueError("grouped K/V heads, the output gate, routed "
                             "experts, latent attention and values of their "
                             "own width or scale need a layer_plan")
        self.layer_plan = layer_plan
        # the PREDICTION MODULE (DeepSeek-V3's multi-token prediction, one
        # module): one more block, stated by its LayerSpec, that reads the
        # main model's last hidden state beside the NEXT token's embedding and
        # scores the token after that through the main model's embedding and
        # head.  A latent block: what it caches is one more latent row a
        # position (``serving``: one more arena layer of the target's pool)
        self.mtp = LayerSpec(*mtp) if mtp is not None else None
        if self.mtp is not None:
            if (layer_plan is None or self.mtp.mixer != "mla" or self.mla is None
                    or self.bias or self.mtp.window
                    or pos_encoding == "learned"
                    or (self.mtp.mlp == "moe" and moe is None)):
                raise ValueError(
                    "a prediction module is one 'mla' block of a planned "
                    "model (mla=MLASpec, no biases, no window, no learned "
                    "positions; a 'moe' half needs moe=MoESpec)")

    # -------------------------------------------------------------- #
    @property
    def head_dim(self) -> int:
        return self._mha.head_dim

    @property
    def plan(self):
        """The layer plan, GPT-2's included (one uniform group)."""
        if self.layer_plan is not None:
            return self.layer_plan
        return ((self.n_layers, (LayerSpec(self.n_head),)),)

    @property
    def moe_layers(self) -> int:
        """How many of the plan's layers are routed expert layers."""
        return sum(r * sum(s.mlp == "moe" for s in period)
                   for r, period in self.plan)

    def _layers_mixing(self, mixer: str) -> tuple:
        specs = [s for r, period in self.plan for s in period * r]
        return tuple(l for l, s in enumerate(specs) if s.mixer == mixer)

    @property
    def kv_layers(self) -> tuple:
        """The (absolute) layers that keep keys and values: a paged pool
        holds one arena layer for each, in this order."""
        return self._layers_mixing("attention")

    @property
    def v_dim(self) -> int:
        """Lanes of a softmax layer's value head."""
        return self.v_head_dim or self.head_dim

    def kv_heads(self, spec: LayerSpec) -> int:
        """A softmax layer's K/V heads: its own, or the model's."""
        return spec.n_kv_head or self.n_kv_head or self.n_head

    @property
    def cache_classes(self) -> tuple:
        """The plan's softmax layers grouped by what they cache and for how
        long (:class:`CacheClass`), in plan order: a paged ``(k, v)`` pool
        holds one class of blocks for each.  A model whose softmax layers are
        all of one kind has one class, of every such layer."""
        specs = [s for r, period in self.plan for s in period * r]
        found: dict = {}
        for l, s in enumerate(specs):
            if s.mixer == "attention":
                found.setdefault(self._class_key(s), []).append(l)
        return tuple(CacheClass(*key, tuple(layers))
                     for key, layers in found.items())

    def _class_key(self, spec: LayerSpec) -> tuple:
        return (self.kv_heads(spec), self.head_dim, self.v_dim, spec.window)

    def cache_class(self, spec: LayerSpec) -> int:
        """Which of :attr:`cache_classes` a softmax layer belongs to."""
        key = self._class_key(spec)
        return next(i for i, c in enumerate(self.cache_classes)
                    if tuple(c[:4]) == key)

    @property
    def state_layers(self) -> tuple:
        """The layers that keep a recurrent state (``"kda"``) instead."""
        return self._layers_mixing("kda")

    @property
    def latent_layers(self) -> tuple:
        """The layers that keep one latent row a position (``"mla"``): a
        paged pool of such rows holds one arena layer for each."""
        return self._layers_mixing("mla")

    @property
    def n_counts(self) -> int:
        """Integers a routed layer counts (``MoESpec.n_counts``)."""
        return 2 if self.moe is None else self.moe.n_counts

    @property
    def state_shapes(self) -> tuple:
        """What one sequence holds a recurrent layer: the state's shape (H,
        d_k, d_v) and the convolution tail's (taps - 1, q, k and v channels);
        the heads are the first recurrent layer's (one shape a model)."""
        spec = next(s for _, period in self.plan for s in period
                    if s.mixer == "kda")
        d = self.head_dim
        return ((spec.n_head, d, d), (self.kda_conv - 1, 3 * spec.n_head * d))

    def group_params(self, params):
        """``params`` by the plan: a list (groups) of lists (the period's
        positions) of layer-stacked parameter dicts."""
        if self.layer_plan is None:
            return [[params["blocks"]]]
        return params["groups"]

    # -------------------------------------------------------------- #
    def _init_block(self, rng):
        ks = jax.random.split(rng, 3)
        h, f = self.hidden_size, self.ffn_size
        std_h, std_f = 1.0 / math.sqrt(h), 1.0 / math.sqrt(f)
        p = {
            "ln1": {"weight": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            "attn": self._mha.init(ks[0]),
            "ln2": {"weight": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        }
        if self.moe_experts:
            from bigdl_tpu.parallel.expert import init_moe_params
            p["moe"] = init_moe_params(ks[1], self.moe_experts, h, f)
        else:
            p["w1"] = jax.random.uniform(ks[1], (h, f), jnp.float32,
                                         -std_h, std_h)
            p["b1"] = jnp.zeros((f,))
            p["w2"] = jax.random.uniform(ks[2], (f, h), jnp.float32,
                                         -std_f, std_f)
            p["b2"] = jnp.zeros((h,))
        return p

    def _init_norm(self):
        h = self.hidden_size
        p = {"weight": jnp.ones((h,))}
        if self.norm == "layernorm":
            p["bias"] = jnp.zeros((h,))
        return p

    def _init_layer(self, spec: LayerSpec, rng):
        """One planned layer: attention of ``spec.n_head`` query heads
        over the model's K/V heads, then its feed-forward half."""
        ks = jax.random.split(rng, 8)
        h, f, d = self.hidden_size, self.ffn_size, self.head_dim
        std_h = 1.0 / math.sqrt(h)
        inner, kv = spec.n_head * d, self.kv_heads(spec) * d

        def mat(k, shape, std):
            return jax.random.normal(k, shape, jnp.float32) * std

        p = {"ln1": self._init_norm(), "ln2": self._init_norm()}
        if spec.mixer == "kda":
            kk = jax.random.split(ks[0], 8)
            proj = lambda k: mat(k, (h, inner), std_h)      # noqa: E731
            p["kda"] = {
                "wq": proj(kk[0]), "wk": proj(kk[1]), "wv": proj(kk[2]),
                "wo": mat(ks[3], (inner, h), 1.0 / math.sqrt(inner)),
                "conv": mat(kk[3], (self.kda_conv, 3 * inner),
                            1.0 / math.sqrt(self.kda_conv)),
                # the decay's rate a head and offset a channel; beta a
                # head; the head norm's weight
                "a_log": jnp.log(jnp.linspace(1.0, 16.0, spec.n_head)),
                "dt_bias": jnp.zeros((inner,)),
                "wb": mat(ks[1], (h, spec.n_head), std_h),
                "norm": jnp.ones((d,))}
            # the decay's and the output gate's projections (KDASpec)
            if self.kda.full_rank:
                p["kda"].update(wf=proj(kk[4]), wg=proj(kk[6]))
            else:
                p["kda"].update(
                    wf1=mat(kk[4], (h, d), std_h),
                    wf2=mat(kk[5], (d, inner), 1.0 / math.sqrt(d)),
                    wg1=mat(kk[6], (h, d), std_h),
                    wg2=mat(kk[7], (d, inner), 1.0 / math.sqrt(d)))
        elif spec.mixer == "mla":
            m, kk = self.mla, jax.random.split(ks[0], 3)
            if m.q_rank:
                # the compressed query: down, an RMSNorm's weight, up
                kq = jax.random.split(kk[0])
                query = {"wq_a": mat(kq[0], (h, m.q_rank), std_h),
                         "q_norm": jnp.ones((m.q_rank,)),
                         "wq_b": mat(kq[1], (m.q_rank,
                                             spec.n_head * m.score_dim),
                                     1.0 / math.sqrt(m.q_rank))}
            else:
                query = {"wq": mat(kk[0], (h, spec.n_head * m.score_dim),
                                   std_h)}
            p["mla"] = {
                **query,
                "w_dkv": mat(kk[1], (h, m.row), std_h),
                "kv_norm": jnp.ones((m.kv_rank,)),
                # a head's [k_n ; v] side by side
                "w_ukv": mat(kk[2], (m.kv_rank, spec.n_head * (m.nope + m.v)),
                             1.0 / math.sqrt(m.kv_rank)),
                "wo": mat(ks[3], (spec.n_head * m.v, h),
                          1.0 / math.sqrt(spec.n_head * m.v))}
            if self.attn_gate:
                p["mla"]["wg"] = mat(ks[4], (h, spec.n_head * m.v
                                             if self.attn_gate == "elementwise"
                                             else spec.n_head), std_h)
        else:
            # values of their own width: wv and wo follow it
            dv = self.v_dim
            out, kv_v = spec.n_head * dv, self.kv_heads(spec) * dv
            attn = {"wq": mat(ks[0], (h, inner), std_h),
                    "wk": mat(ks[1], (h, kv), std_h),
                    "wv": mat(ks[2], (h, kv_v), std_h),
                    "wo": mat(ks[3], (out, h), 1.0 / math.sqrt(out))}
            if self.attn_gate:
                attn["wg"] = mat(ks[4], (h, out if self.attn_gate
                                         == "elementwise" else spec.n_head),
                                 std_h)
            if self.bias:
                attn.update(bq=jnp.zeros((inner,)), bk=jnp.zeros((kv,)),
                            bv=jnp.zeros((kv_v,)), bo=jnp.zeros((h,)))
            if spec.sink:       # one logit a query head, float32 always
                attn["sink"] = jnp.zeros((spec.n_head,), jnp.float32)
            p["attn"] = attn
        if spec.mlp == "moe":
            from bigdl_tpu.parallel.expert import init_routed_params
            p["moe"] = init_routed_params(ks[5], self.moe, h)
        elif self.mlp_act == "swiglu":
            p["mlp"] = {"w_gate": mat(ks[5], (h, f), std_h),
                        "w_up": mat(ks[6], (h, f), std_h),
                        "w_down": mat(ks[7], (f, h), 1.0 / math.sqrt(f))}
        else:
            p.update(w1=mat(ks[5], (h, f), std_h), b1=jnp.zeros((f,)),
                     w2=mat(ks[6], (f, h), 1.0 / math.sqrt(f)),
                     b2=jnp.zeros((h,)))
        return p

    def _mlp(self, bp, m):
        """The block's feed-forward half: dense GELU MLP or switch MoE.
        Shared by the single-device block, the sequence-parallel body
        (token-local either way), and cached generation.  Returns
        (out, aux) — aux is 0 for the dense path."""
        if self.moe_experts:
            from bigdl_tpu.parallel.expert import switch_mlp
            return switch_mlp(bp["moe"], m,
                              capacity_factor=self.moe_capacity_factor,
                              balance_axis=self.moe_balance_axis)
        from bigdl_tpu.quant.kernels import qmatmul
        m = jax.nn.gelu(qmatmul(m, bp["w1"]) + bp["b1"], approximate=True)
        return qmatmul(m, bp["w2"]) + bp["b2"], jnp.zeros((), jnp.float32)

    def init(self, rng):
        k_emb, k_pos, k_head, k_blocks = jax.random.split(rng, 4)
        h, v = self.hidden_size, self.vocab_size
        std = 1.0 / math.sqrt(h)
        # one vmapped init -> parameters already stacked on a leading
        # layer axis, the exact layout lax.scan consumes
        p = {"embed": jax.random.normal(k_emb, (v, h)) * std,
             "ln_f": self._init_norm()}
        if self.layer_plan is None:
            p["blocks"] = jax.vmap(self._init_block)(
                jax.random.split(k_blocks, self.n_layers))
        else:
            import functools
            keys = iter(jax.random.split(k_blocks, self.n_layers))
            p["groups"] = [
                [jax.vmap(functools.partial(self._init_layer, spec))(
                    jnp.stack([next(keys) for _ in range(repeat)]))
                 for spec in period]
                for repeat, period in self.layer_plan]
        if self.pos_encoding == "learned":
            p["pos"] = jax.random.normal(k_pos, (self.max_len, h)) * std
        if not self.tie_embeddings:
            p["head"] = jax.random.uniform(k_head, (h, v), jnp.float32,
                                           -std, std)
        if self.mtp is not None:
            k_eh, k_blk = jax.random.split(jax.random.fold_in(rng, 7))
            p["mtp"] = {"enorm": self._init_norm(), "hnorm": self._init_norm(),
                        "eh_proj": jax.random.normal(k_eh, (2 * h, h))
                        / math.sqrt(2 * h),
                        "block": self._init_layer(self.mtp, k_blk),
                        "norm": self._init_norm()}
        return p

    # -------------------------------------------------------------- #
    @staticmethod
    def _layer_norm(p, x):
        from bigdl_tpu.nn.normalization import layer_norm
        return layer_norm(x, p["weight"], p["bias"])

    def _norm(self, p, x):
        """The block's normalisation: LayerNorm, or RMSNorm (f32 inside,
        a learned weight, no offset)."""
        if self.norm == "layernorm":
            return self._layer_norm(p, x)
        return self._rms_norm(p["weight"], x)

    def _rms_norm(self, weight, x):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.norm_eps)
        return (y * weight.astype(jnp.float32)).astype(x.dtype)

    def _rope(self, q, k, positions, spec: Optional[LayerSpec] = None,
              dim: Optional[int] = None):
        """Rotate q and k at ``positions``: by the layer's own rotary
        embedding where its spec has one, else by ``pos_encoding``; ``dim``:
        the lanes handed in where they are not a whole head (a latent
        layer's rotated lanes)."""
        if spec is not None and spec.rope is not None:
            inv_freq = spec.rope.inv_freq(dim or self.head_dim)
            scale = spec.rope.attention_factor
            return (apply_rotary(q, positions, inv_freq, scale),
                    apply_rotary(k, positions, inv_freq, scale))
        if self.pos_encoding != "rope":
            return q, k
        return (apply_rope(q, positions, self.rope_base),
                apply_rope(k, positions, self.rope_base))

    # -- one layer in three parts, shared by the training forward, the
    # -- prefills and every cached step (models/transformer/generate.py):
    # -- what differs between them is only how attention reads its keys
    def layer_qkv(self, spec: LayerSpec, bp, x, positions=None):
        """Pre-attention: norm, projections, rotary.  -> q (B, H, T, D),
        k (B, H_kv, T, D), v (B, H_kv, T, D_v) -- the layer's own count of
        K/V heads, the values at their own width and SCALED
        (``value_scale``): what a position caches -- gate (B, T, H), (B, T,
        H * D_v) where it is elementwise, or None."""
        from bigdl_tpu.nn._util import match_compute_dtype
        from bigdl_tpu.quant.kernels import qmatmul
        ap = bp["attn"]
        a = match_compute_dtype(self._norm(bp["ln1"], x), ap["wq"])
        q, k, v = (qmatmul(a, ap[n]) for n in ("wq", "wk", "wv"))
        if self.bias:
            q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
        b, t = a.shape[:2]

        def heads(y, n):    # (B, T, n * D) -> (B, n, T, D)
            return y.reshape(b, t, n, -1).transpose(0, 2, 1, 3)

        n_kv = self.kv_heads(spec)
        q, k, v = heads(q, spec.n_head), heads(k, n_kv), heads(v, n_kv)
        if self.value_scale != 1.0:
            v = (v.astype(jnp.float32) * self.value_scale).astype(v.dtype)
        if positions is not None:
            q, k = self._rope(q, k, positions, spec)
        gate = (jax.nn.sigmoid(qmatmul(a, ap["wg"]).astype(jnp.float32))
                if self.attn_gate else None)
        return q, k, v, gate

    def layer_attn_out(self, bp, o, gate=None):
        """Post-attention, before the residual: the gate (a scalar a head,
        or elementwise) and the output projection (an ``"mla"`` layer's
        where ``bp`` is one's).  ``o`` (B, H, T, D)."""
        from bigdl_tpu.quant.kernels import qmatmul
        ap = bp["mla"] if "mla" in bp else bp["attn"]
        b, h, t, d = o.shape
        if gate is not None and self.attn_gate == "elementwise":
            gate = gate.reshape(b, t, h, d).transpose(0, 2, 1, 3)
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
        elif gate is not None:
            o = (o.astype(jnp.float32)
                 * gate.transpose(0, 2, 1)[..., None]).astype(o.dtype)
        y = qmatmul(o.transpose(0, 2, 1, 3).reshape(b, t, h * d), ap["wo"])
        if self.bias:
            y = y + ap["bo"]
        return y

    # -- an "mla" layer's mixer: what comes before attention, and the two
    # -- forms' own steps (MLASpec); after it, layer_attn_out
    def mla_inputs(self, spec: LayerSpec, bp, x, positions=None):
        """Norm, projections, the latent's norm, rotary.  -> q (B, H, T,
        nope + rope) with its last ``rope`` lanes rotated, row (B, T,
        kv_rank + rope) = ``[c ; k_r]`` -- what a position caches, in the
        compute dtype -- and the gate as :meth:`layer_qkv` hands it."""
        from bigdl_tpu.nn._util import match_compute_dtype
        from bigdl_tpu.quant.kernels import qmatmul
        mp, m = bp["mla"], self.mla
        a = match_compute_dtype(self._norm(bp["ln1"], x),
                                mp["wq_a" if m.q_rank else "wq"])
        b, t = a.shape[:2]
        if m.q_rank:
            with jax.named_scope("mla/q_down"):
                q = self._rms_norm(mp["q_norm"], qmatmul(a, mp["wq_a"]))
            q = qmatmul(q, mp["wq_b"])
        else:
            q = qmatmul(a, mp["wq"])
        q = q.reshape(b, t, spec.n_head, m.score_dim).transpose(0, 2, 1, 3)
        down = qmatmul(a, mp["w_dkv"])
        c = self._rms_norm(mp["kv_norm"], down[..., :m.kv_rank])
        k_r = down[..., None, :, m.kv_rank:]        # one "head": (B, 1, T, rope)
        if positions is not None:
            q_r, k_r = self._rope(q[..., m.nope:], k_r, positions, spec,
                                  m.rope)
            q = jnp.concatenate([q[..., :m.nope], q_r], -1)
        row = jnp.concatenate([c, k_r[..., 0, :, :]], -1)
        gate = (jax.nn.sigmoid(qmatmul(a, mp["wg"]).astype(jnp.float32))
                if self.attn_gate else None)
        return q, row, gate

    def _mla_up(self, bp):
        """``W_ukv`` as stored, by head: -> (W_uk (kv_rank, H, nope), W_uv
        (kv_rank, H, v))."""
        m = self.mla
        w = bp["mla"]["w_ukv"]
        w = w.reshape(m.kv_rank, -1, m.nope + m.v)
        return w[..., :m.nope], w[..., m.nope:]

    def mla_expand(self, bp, row):
        """EXPANDED: cached rows (B, T, kv_rank + rope) -> k (B, H, T, nope +
        rope), the rotated lanes shared by every head, and v (B, H, T, v)."""
        from bigdl_tpu.quant.kernels import qmatmul
        m = self.mla
        with jax.named_scope("mla/expand"):
            b, t = row.shape[:2]
            kv = qmatmul(row[..., :m.kv_rank], bp["mla"]["w_ukv"]).reshape(
                b, t, -1, m.nope + m.v).transpose(0, 2, 1, 3)
            k_r = jnp.broadcast_to(row[:, None, :, m.kv_rank:],
                                   kv.shape[:3] + (m.rope,))
            return (jnp.concatenate([kv[..., :m.nope], k_r.astype(kv.dtype)],
                                    -1), kv[..., m.nope:])

    def mla_absorb(self, bp, q):
        """ABSORBED, before the softmax: ``q`` (..., H, W, nope + rope) ->
        float32 (..., H, W, kv_rank + rope): ``W_uk`` folded into the first
        lanes, so that a query meets the cached ROW itself."""
        m = self.mla
        with jax.named_scope("mla/absorb"):
            q = q.astype(jnp.float32)
            folded = jnp.einsum("...hwn,rhn->...hwr", q[..., :m.nope],
                                self._mla_up(bp)[0].astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            return jnp.concatenate([folded, q[..., m.nope:]], -1)

    def mla_values(self, bp, u):
        """ABSORBED, after it: the weighted latents ``u`` (..., H, W, kv_rank)
        float32 -> o (..., H, W, v) float32 through ``W_uv``."""
        with jax.named_scope("mla/absorb"):
            return jnp.einsum("...hwr,rhv->...hwv", u,
                              self._mla_up(bp)[1].astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)

    def latent_parts(self, q, k, v, mask):
        """A softmax's three parts over one block of keys, float32 scores
        whatever the operands' dtype: ``q`` (..., Tq, D), ``k`` (..., Tk, D),
        ``v`` (..., Tk, Dv) (a latent layer's score and value widths differ),
        ``mask`` broadcastable to (..., Tq, Tk) -> (maximum (..., Tq), sum of
        ``exp(score - maximum)``, the values weighted by it (..., Tq, Dv)), the
        shapes :func:`~bigdl_tpu.nn.attention.online_softmax_update` merges."""
        from bigdl_tpu.nn.attention import _block_scores
        with jax.named_scope("mla/attend"):
            return _block_scores(q, k, v, mask, 1.0 / math.sqrt(q.shape[-1]),
                                 acc=jnp.float32)

    def attend_latent(self, bp, q, row, segment_ids=None):
        """An ``"mla"`` layer's self-attention of whole sequences, EXPANDED,
        on the XLA path: one masked (T, T) float32 score matrix a head (the
        flash kernel takes one width for scores and values).  ``q`` and
        ``row`` as :meth:`mla_inputs` hands them -> o (B, H, T, v)."""
        from bigdl_tpu.nn.attention import _finalize, segment_mask
        k, v = self.mla_expand(bp, row)
        pos = jnp.arange(q.shape[-2])
        mask = window_mask(pos, pos, None)
        if segment_ids is not None:
            mask = mask & segment_mask(segment_ids, segment_ids)
        _, den, o = self.latent_parts(q, k, v, mask)
        return _finalize(o, den).astype(q.dtype)

    # -- a "kda" layer's mixer in three parts: what comes before the
    # -- recurrence, the heads it reads, and what follows it
    def kda_inputs(self, bp, x):
        """Norm and projections of a recurrent layer: -> (qkv (B, T, 3 * H *
        D) before the convolution, g (B, T, H, D) float32 the log of the
        decay a channel, beta (B, T, H) float32 in (0, ``kda.beta_scale``),
        gate (B, T, H * D) float32); the decay's form and the projections'
        rank are the model's :class:`KDASpec`."""
        from bigdl_tpu.nn._util import match_compute_dtype
        from bigdl_tpu.quant.kernels import qmatmul
        kp = bp["kda"]
        a = match_compute_dtype(self._norm(bp["ln1"], x), kp["wq"])
        qkv = jnp.concatenate([qmatmul(a, kp[n]) for n in ("wq", "wk", "wv")],
                              axis=-1)
        d = self.head_dim
        f32 = lambda y: y.astype(jnp.float32)               # noqa: E731
        rate = jnp.exp(f32(kp["a_log"]))[:, None]
        full = self.kda.full_rank
        project = lambda n: (qmatmul(a, kp[n]) if full else         # noqa: E731
                             qmatmul(qmatmul(a, kp[n + "1"]), kp[n + "2"]))
        fgt = f32(project("wf")) + f32(kp["dt_bias"])
        by_head = fgt.shape[:-1] + (-1, d)
        if self.kda.gate == "bounded":
            g = self.kda.lower_bound * jax.nn.sigmoid(rate * fgt.reshape(by_head))
        else:
            g = -rate * jax.nn.softplus(fgt.reshape(by_head))
        beta = self.kda.beta_scale * jax.nn.sigmoid(f32(qmatmul(a, kp["wb"])))
        gate = jax.nn.sigmoid(f32(project("wg")))
        return qkv, g, beta, gate

    def kda_heads(self, y):
        """After the convolution: ``y`` (..., 3 * H * D) float32 -> q, k
        (l2-normalised a head) and v, each (..., H, D) float32."""
        from bigdl_tpu.nn.kda import l2norm
        q, k, v = (z.reshape(z.shape[:-1] + (-1, self.head_dim))
                   for z in jnp.split(jax.nn.silu(y), 3, axis=-1))
        return l2norm(q), l2norm(k), v

    def kda_out(self, bp, o, gate, dtype):
        """After the recurrence, before the residual: RMSNorm a head (one
        weight vector), the elementwise gate, the output projection.  ``o``
        (..., H, D) float32."""
        from bigdl_tpu.quant.kernels import qmatmul
        kp = bp["kda"]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.norm_eps) * kp["norm"].astype(jnp.float32)
        o = o.reshape(gate.shape) * gate
        return qmatmul(o.astype(dtype), kp["wo"])

    def layer_kda(self, bp, x, state=None, tail=None, length=None):
        """A recurrent layer's mixer over whole sequences ``x`` (B, T,
        hidden), from ``state`` (B, H, D, D) float32 and the convolution's
        ``tail`` (B, taps - 1, 3 * H * D) (``None``: a sequence's start).
        ``length`` (traced) says how many leading positions are real: what
        lies past them is bucket padding, which neither the state nor the
        tail handed out has seen.  -> (y before the residual, state, tail)."""
        from bigdl_tpu.nn.kda import kda_chunked, short_conv
        qkv, g, beta, gate = self.kda_inputs(bp, x)
        y, tail = short_conv(qkv, bp["kda"]["conv"], tail, length)
        q, k, v = self.kda_heads(y)
        valid = (None if length is None else jnp.broadcast_to(
            jnp.arange(x.shape[1])[None, :] < jnp.reshape(length, (-1, 1)),
            x.shape[:2]))
        o, state = kda_chunked(q, k, v, g, beta, state, valid)
        return self.kda_out(bp, o, gate, x.dtype), state, tail

    def layer_kda_step(self, bp, x, state, tail, layer, active):
        """One new position a sequence: ``x`` (S, 1, hidden), ``state`` the
        state ARENA (R, S, H, D, D) and ``layer`` (traced) this layer's index
        in it, ``tail`` (S, taps - 1, 3 * H * D) the layer's tail rows; a row
        that is not ``active`` (S,) keeps its state and tail.  The recurrence
        by ``ops.kda_step.kda_step_path``: on a TPU the Pallas kernel that
        reads and writes the active slots' rows where they lie, else
        ``nn.kda.kda_step`` on the layer's rows.  -> (y (S, 1, hidden), the
        arena, the layer's tail rows)."""
        from bigdl_tpu.nn.kda import kda_step, short_conv_step
        from bigdl_tpu.ops.kda_step import kda_step_path, kda_step_rows
        qkv, g, beta, gate = self.kda_inputs(bp, x)
        y, new_tail = short_conv_step(qkv[:, 0], bp["kda"]["conv"], tail)
        q, k, v = self.kda_heads(y)
        if kda_step_path(*state.shape[2:]) == "kernel":
            with jax.named_scope("kda/step"):
                o, state = kda_step_rows(state, layer, active, q, k, v,
                                         g[:, 0], beta[:, 0])
        else:
            row = state[layer]
            o, new = kda_step(q, k, v, g[:, 0], beta[:, 0], row)
            state = state.at[layer].set(
                jnp.where(active[:, None, None, None], new, row))
        new_tail = jnp.where(active[:, None, None], new_tail, tail)
        y = self.kda_out(bp, o[:, None], gate, x.dtype)
        return y, state, new_tail.astype(tail.dtype)

    def layer_ffn(self, spec: LayerSpec, bp, x, *, dense_routing=False,
                  token_mask=None):
        """The feed-forward half before its residual: -> (m, aux, counts).
        ``aux`` is the switch MoE's balance term (0 otherwise), ``counts``
        the routed layer's integers (``parallel.expert.routed_experts``;
        zeros otherwise).  ``dense_routing``: the cached steps run the
        legacy switch MoE without its capacity window."""
        m = self._norm(bp["ln2"], x)
        zero = jnp.zeros((), jnp.float32)
        counts = jnp.zeros((self.n_counts,), jnp.int32)
        if spec.mlp == "moe":
            from bigdl_tpu.parallel.expert import routed_mlp
            m, counts = routed_mlp(bp["moe"], m, self.moe,
                                   token_mask=token_mask)
            return m, zero, counts
        if self.mlp_act == "swiglu":
            from bigdl_tpu.parallel.expert import swiglu
            return swiglu(bp["mlp"], m), zero, counts
        if self.moe_experts and dense_routing:
            from bigdl_tpu.parallel.expert import switch_mlp
            m, _ = switch_mlp(bp["moe"], m, capacity_factor=None)
            return m, zero, counts
        m, aux = self._mlp(bp, m)
        return m, aux, counts

    def layer_sink(self, spec: LayerSpec, bp):
        """The layer's sink logits (H,) float32, or None where it has none."""
        return bp["attn"]["sink"].astype(jnp.float32) if spec.sink else None

    def attend_parts(self, q, k, v, mask):
        """A softmax's three parts over one block of keys whose K/V heads are
        shared by GROUPS of query heads, float32 scores whatever the operands'
        dtype, a K/V head at a time (its group's (G, Tq, Tk) scores are all
        that is alive at once): ``q`` (B, H, Tq, D), ``k`` (B, H_kv, Tk, D),
        ``v`` (B, H_kv, Tk, D_v), ``mask`` (Tq, Tk) -> (maximum (B, H, Tq),
        sum, weighted values (B, H, Tq, D_v)), the shapes
        :func:`~bigdl_tpu.nn.attention.online_softmax_update` merges."""
        from bigdl_tpu.nn.attention import _block_scores
        b, h, tq, d = q.shape
        n_kv = k.shape[1]
        qg = q.reshape(b, n_kv, h // n_kv, tq, d)
        scale = 1.0 / math.sqrt(d)

        def head(x):    # (B, G, Tq, D), (B, Tk, D), (B, Tk, D_v)
            return _block_scores(x[0], x[1][:, None], x[2][:, None], mask,
                                 scale, acc=jnp.float32)

        top, den, o = jax.lax.map(head, (jnp.moveaxis(qg, 1, 0),
                                         jnp.moveaxis(k, 1, 0),
                                         jnp.moveaxis(v, 1, 0)))
        flat = lambda x: jnp.moveaxis(x, 0, 1).reshape(    # noqa: E731
            (b, h) + x.shape[3:])
        return flat(top), flat(den), flat(o)

    @staticmethod
    def sink_parts(sink, like):
        """The sink as one more block of a softmax: its logit a head, a
        probability mass of its own and NO value.  ``like``: a block's parts
        (maximum (B, H, Tq), .., weighted values)."""
        top = jnp.broadcast_to(sink.reshape((1, -1) + (1,) * (like[0].ndim - 2)),
                               like[0].shape)
        return top, jnp.ones_like(like[1]), jnp.zeros_like(like[2])

    def attend_full(self, spec: LayerSpec, q, k, v, segment_ids=None,
                    sink=None):
        """Self-attention of a whole sequence (training forward, prefill):
        causal, within ``spec.window`` where the layer has one, K/V heads
        shared by groups of query heads.  The Pallas flash kernel where
        the model's ``attention_impl`` resolves to it (the mask terms are
        applied inside its tiles and K/V tiles are read once a group: no
        (T, T) matrix and no repeated heads in HBM), else one XLA fusion
        under an explicit mask.  A layer with a ``sink`` (H,) or with values
        narrower than its keys takes the XLA path, a K/V head at a time,
        float32 scores (the flash kernel takes one width and no column
        without a key)."""
        mha = self._mha
        if sink is not None or v.shape[-1] != q.shape[-1]:
            from bigdl_tpu.nn.attention import (_finalize,
                                                online_softmax_update,
                                                segment_mask)
            if segment_ids is not None:
                raise NotImplementedError(
                    "packed documents under a sink or values of their own "
                    "width")
            pos = jnp.arange(q.shape[-2])
            top, den, o = self.attend_parts(
                q, k, v, window_mask(pos, pos, spec.window))
            if sink is not None:
                with jax.named_scope("attn/sink"):
                    o, den, _ = online_softmax_update(
                        (o, den, top), self.sink_parts(sink, (top, den, o)))
            return _finalize(o, den).astype(q.dtype)
        if spec.window is None and q.shape[1] == k.shape[1]:
            # one shared dispatch (nn.MultiHeadAttention.attend); the block
            # keeps mha.block_size as flash TILES, never the blockwise core
            return mha.attend(q, k, v, segment_ids=segment_ids,
                              allow_blockwise=False)
        if mha.resolve_use_flash(q.shape[-2]):
            from bigdl_tpu.ops import flash_attention
            bs = mha.block_size or 128
            return flash_attention(q, k, v, causal=True, window=spec.window,
                                   segment_ids=segment_ids, block_q=bs,
                                   block_k=bs)
        from bigdl_tpu.nn.attention import (dot_product_attention,
                                            segment_mask)
        group = q.shape[1] // k.shape[1]
        if group > 1:
            k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        pos = jnp.arange(q.shape[-2])
        mask = window_mask(pos, pos, spec.window)
        if segment_ids is not None:
            mask = mask & segment_mask(segment_ids, segment_ids)
        return dot_product_attention(q, k, v, mask=mask)

    def _block(self, spec, bp, x, training: bool, rng, positions=None,
               segment_ids=None):
        if spec.mixer == "kda":
            if segment_ids is not None:
                raise NotImplementedError(
                    "a recurrent layer does not reset its state at a packed "
                    "document's start")
            mixed = self.layer_kda(bp, x)[0]
        elif spec.mixer == "mla":
            q, row, gate = self.mla_inputs(spec, bp, x, positions)
            mixed = self.layer_attn_out(
                bp, self.attend_latent(bp, q, row, segment_ids), gate)
        else:
            q, k, v, gate = self.layer_qkv(spec, bp, x, positions)
            o = self.attend_full(spec, q, k, v, segment_ids,
                                 self.layer_sink(spec, bp))
            mixed = self.layer_attn_out(bp, o, gate)

        def drop(y, rng):
            if not (training and self.dropout > 0.0):
                return y, rng
            rng, sub = jax.random.split(rng)
            keep = 1.0 - self.dropout
            return y * jax.random.bernoulli(sub, keep, y.shape) / keep, rng

        o, rng = drop(mixed, rng)
        x = x + o
        m, aux, _ = self.layer_ffn(spec, bp, x)
        m, rng = drop(m, rng)
        return x + m, aux

    def _trunk(self, params, x, training: bool, rng):
        """Embedding and every block: 1-based ``x`` (B, T) -> (the last
        block's output BEFORE ``ln_f`` (B, T, hidden), aux)."""
        ids = jnp.asarray(x)
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        ids = ids - 1  # 1-based API edge -> 0-based gather
        t = ids.shape[-1]
        h = params["embed"][ids]
        if self.pos_encoding == "learned":
            h = h + params["pos"][:t]
        positions = jnp.arange(t)
        if rng is None:
            if training and self.dropout > 0.0:
                raise ValueError(
                    "TransformerLM with dropout>0 needs an rng in training "
                    "mode — a silent fixed key would apply the identical "
                    "dropout mask every step")
            rng = jax.random.PRNGKey(0)

        segment_ids = None
        if self.doc_start_id is not None:
            # ids are already 0-based here; the marker id came in 1-based
            segment_ids = jnp.cumsum(
                (ids == self.doc_start_id - 1).astype(jnp.int32), axis=-1)

        block = (jax.checkpoint(self._block, static_argnums=(0, 3))
                 if self.remat else self._block)
        keys = jax.random.split(rng, self.n_layers)
        aux, done = jnp.zeros((), jnp.float32), 0
        for (repeat, period), stacks in zip(self.plan,
                                            self.group_params(params)):
            n = repeat * len(period)

            def body(carry, xs, period=period):
                h, aux = carry
                for i, (spec, bp) in enumerate(zip(period, xs[0])):
                    h, a = block(spec, bp, h, training, xs[1][i], positions,
                                 segment_ids)
                    aux = aux + a
                return (h, aux), None

            # one key a layer, in the plan's order: (repeat, period, ...)
            group_keys = keys[done:done + n].reshape(
                (repeat, len(period)) + keys.shape[1:])
            (h, aux), _ = jax.lax.scan(body, (h, aux), (stacks, group_keys))
            done += n
        return h, aux

    def _head(self, params, h):
        """``ln_f``'s output -> logits through the (tied or own) head."""
        if self.tie_embeddings:
            logits = h @ params["embed"].T.astype(h.dtype)
        else:
            from bigdl_tpu.quant import is_qtensor
            from bigdl_tpu.quant.kernels import qmatmul
            head = params["head"]
            logits = (qmatmul(h, head) if is_qtensor(head)
                      else h @ head.astype(h.dtype))
        return logits

    def _forward(self, params, x, training: bool, rng):
        h, aux = self._trunk(params, x, training, rng)
        logits = self._head(params, self._norm(params["ln_f"], h))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return logp, aux

    # -- the prediction module: pair t is (the main model's hidden state at
    # -- t, the token at t + 1); its block's output scores the token at t + 2
    def mtp_embed(self, params, h, next_ids0):
        """A pair's input: ``h`` (..., hidden) the main model's last block's
        output BEFORE ``ln_f``, ``next_ids0`` (...,) 0-based ids of the tokens
        that follow -> ``[rmsnorm_e(Emb(x)) ; rmsnorm_h(h)] W_eh`` (...,
        hidden), the embedding's half first."""
        from bigdl_tpu.quant.kernels import qmatmul
        mp = params["mtp"]
        with jax.named_scope("mtp/embed_proj"):
            e = self._norm(mp["enorm"], params["embed"][next_ids0])
            z = jnp.concatenate(
                [e, self._norm(mp["hnorm"], h).astype(e.dtype)], -1)
            return qmatmul(z, mp["eh_proj"])

    def mtp_logits(self, params, g):
        """The module's block's output ``g`` -> draft logits, through its own
        final norm and the MAIN model's head."""
        with jax.named_scope("mtp/head"):
            return self._head(params, self._norm(params["mtp"]["norm"], g))

    def mtp_forward(self, params, x):
        """The module over whole sequences: 1-based ``x`` (B, T) -> logits (B,
        T - 1, vocab) float32, row t (from the pair of the main model's hidden
        state at t and the token at t + 1, causal over the pairs before it,
        rotated at t) scoring the token at t + 2."""
        ids0 = jnp.asarray(x).astype(jnp.int32) - 1
        h, _ = self._trunk(params, x, False, None)
        z = self.mtp_embed(params, h[:, :-1], ids0[:, 1:])
        with jax.named_scope("mtp/block"):
            g, _ = self._block(self.mtp, params["mtp"]["block"], z, False, None,
                               jnp.arange(z.shape[1]))
        return self.mtp_logits(params, g).astype(jnp.float32)

    def f(self, params, x, *, training: bool = False, rng=None):
        return self._forward(params, x, training, rng)[0]

    def apply(self, params, x, *, buffers=None, training: bool = False,
              rng=None):
        """MoE models report the load-balancing term through the reserved
        "aux_loss" buffers key (pre-scaled by ``moe_aux_weight``); the
        optimizers add it to the training loss inside the differentiated
        step, so the gate gradient flows through the standard
        Optimizer/Criterion machinery."""
        y, aux = self._forward(params, x, training, rng)
        new_buffers = dict(buffers) if buffers else {}
        if self.moe_experts:
            new_buffers["aux_loss"] = self.moe_aux_weight * aux
        return y, new_buffers

    def init_buffers(self):
        if self.moe_experts:
            return {"aux_loss": jnp.zeros((), jnp.float32)}
        return {}
