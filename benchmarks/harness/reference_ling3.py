"""Plain Ling-3.0-flash-VL, the language model (inclusionAI; the equations are
written out from its public ``config.json`` and, where that is silent, from
the family's published conventions: every such choice is under ``assumed`` in
the configuration file): seeded weights and the full causal forward in
``jax.numpy``, float32, ``default_matmul_precision("highest")``.  No kernels,
no cache, no chunks, no absorbed form, nothing imported from the program (and
no batching but one: replayed requests of one length step the KDA layers'
literal scan together, ``layer_forward_group``).

What the configuration file says is what runs: ``num_hidden_layers`` layers,
layer l an MLA layer iff ``(l + 1) % layer_group_size == 0`` (one in six), else
a KDA layer; RMSNorm (eps 1e-6), pre-norm residual; the feed-forward half a
dense SwiGLU of ``intermediate_size`` for l < ``first_k_dense_replace``, else
routed.  With ``a = norm(x)``:

*MLA layer* (latent attention, EXPANDED: keys and values up-projected, one
full masked score matrix a head, rows in blocks so that 32k positions fit)::

    q_h = (a Wq)_h = [q_n (128) ; q_r' (64)]       [c' ; k_r'] = a W_dkv  (512 + 64)
    c = rmsnorm(c') * kv_norm     q_r, k_r = rotary(q_r', k_r')  theta 6e6, all 64 lanes
    [k_n,h ; v_h] = c W_ukv,h                      (128 + 128 a head; k_r shared)
    s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(192),  j <= t
    out = concat_h(softmax(s_h) v_h * sigmoid((a Wg)_h)) Wo       (a gate a head)

*KDA layer* (gated delta rule, a per-channel BOUNDED decay), THE LITERAL
RECURRENCE, a ``lax.scan`` over positions, a head's state S in R^{128 x 128}::

    q_t, k_t = l2norm(silu(conv4(a Wq)))_t, l2norm(silu(conv4(a Wk)))_t
    v_t = silu(conv4(a Wv))_t                 (depthwise, causal, 4 taps)
    g_t = kda_lower_bound * sigmoid(exp(A_log_h) * ((a Wf)_t + dt_bias))   in (-5, 0)
    beta_t = sigmoid((a Wb)_t)                                             in (0, 1)
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(128)
    out = (rmsnorm_head(o_t) * sigmoid((a Wg)_t)) Wo      (Wf, Wg full rank)

*Routed half*, the router's five steps: ``s = sigmoid(x Wr)`` over all
``experts_published`` experts in float32; ``s' = s + bias``; the experts form
``n_group`` groups of consecutive ones and a group scores the sum of its two
largest ``s'``; the ``topk_group`` best groups stay, the others' ``s'`` are
masked out; the ``num_experts_per_tok`` largest ``s'`` that remain are chosen,
weighed by the UNBIASED ``s`` renormalised (``norm_topk_prob``) times
``routed_scaling_factor``; plus one shared expert.  THE CHIP'S SHARE: of the
routed sum only the experts held here are added (``expert_share = [i, n]``:
experts ``i * num_experts`` onward, of the deployment's n holders: one router
GROUP a chip), and the head is the held slice of the vocabulary; what the
absent experts would add is left out, in the program and here alike.

The seeded weights are chosen so that the check reads precision, as
``reference_solar2`` explains for its own (near-twin head columns over three
decades, a constant stream channel 0, half a stream a sublayer, a router of
uneven column norms that is the same for every seed and whose scores lie well
under 1/2, betas a head seldom raises so that a state kept in bfloat16
shows).  What differs here: the router's gains are uneven across GROUPS as
well as experts (``GROUP_SIGMA``), so that which groups stay differs by token
and the group step changes the picks; the decays lie across the whole of
(-5, 0) (``DECAY_A_STEP``: log-uniform by channel from 1/4,000 to 4.5 a step);
and the latent layer's scores are sharp enough (``MLA_SCORE_SPREAD``) that at
16k-32k positions a query still reads a few tens of them, so that what the
cache holds of a position shows in the stream.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference_gpt2 import gaps_below_best  # noqa: F401  (the check's reading, shared)

TWIN_SPREADS = (1e-4, 1e-1)  # of a twin column about its neighbour, in spreads
# the variance of a latent layer's score before the softmax: at 13 a query
# at 16k-32k positions reads 19-27 of them (1 / sum p^2, median)
MLA_SCORE_SPREAD = 13.0
# on Wo: a softmax over tens of positions averages v down; with the spread
# above the sublayer adds 0.49-0.57 of the stream at those positions (at a
# spread of 7, PR 35's first, 100-170 positions and 0.25-0.29: dropping the
# rotated lanes then read as nearly sound, PERF.md)
MLA_OUT_GAIN = 3.0
ROUTER_SPREAD = 2.0         # of a router logit, mean over experts
ROUTER_SHIFT = 8.0          # what the constant stream channel takes off every logit
EXPERT_SIGMA = 0.5          # log-normal gain of a router column, by expert
GROUP_SIGMA = 0.3           # ... and by group
BIAS_SPREAD = 0.005         # of the router's selection bias
SUBLAYER = 0.5              # what a sublayer adds, relative to a unit stream
DECAY_A_STEP = (1.0 / 4000.0, 4.5)  # -g a step, log-uniform by channel
DECAY_SWING = 0.5           # spread of the decay's data-dependent logit
BETA_SPREAD = 8.0           # of beta's logit (beta = sigmoid of it)
BETA_SHIFT = 14.0           # what the constant stream channel takes off beta's logit
MLA_ROWS = 256              # query rows of a latent layer's score matrix at a time


# -- the configuration, by layer ---------------------------------------------
def layer_kinds(c: dict) -> list:
    """["mla" | "kda"] for the layers that are run."""
    return ["mla" if (l + 1) % c["layer_group_size"] == 0 else "kda"
            for l in range(c["num_hidden_layers"])]


def is_dense(c: dict, layer: int) -> bool:
    return layer < c["first_k_dense_replace"]


def held_experts(c: dict):
    """(first, count) of the routed experts held here."""
    return c["expert_share"][0] * c["num_experts"], c["num_experts"]


def kda_shape(c: dict):
    """(heads, head_dim, conv taps) of a KDA layer."""
    return c["num_attention_heads"], c["head_dim"], c["short_conv_kernel_size"]


# -- seeded weights ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


_NAMES = ("embed", "head", "twins", "twin_spreads", "norm_f", "ln1", "ln2",
          "wq", "wk", "wv", "wo", "wg", "router", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down", "conv", "wf", "a_log", "dt_bias", "wb",
          "norm", "w_dkv", "kv_norm", "w_ukv", "d_gate", "d_up", "d_down")


def _key(seed: int, layer: int, name: str):
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), layer + 1)
    return jax.random.fold_in(key, _NAMES.index(name))


def _swiglu_stds(h: int, f: int):
    # gate and up of spread 1 from a unit input; silu(g) * u has a root mean
    # square of about 0.6, so the down projection's rows bring it to SUBLAYER
    return 1.0 / math.sqrt(h), 1.0 / math.sqrt(h), SUBLAYER / (0.6 * math.sqrt(f))


def _quantiles(n: int, layer: int, salt: int) -> np.ndarray:
    """The normal's quantiles at the n mid-points in an order that depends on
    the layer alone: a pattern that is the SAME for every seed."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.random.RandomState(salt + layer).permutation(z)


def _router_gains(c: dict, layer: int) -> np.ndarray:
    """Log-normal column gains, by expert and by GROUP, the same for every
    seed: how unevenly tokens spread over the experts and over the groups --
    and so which groups stay, and how many picks land on the held one -- does
    not change with the seed (a seed draws the directions)."""
    n, groups = c["experts_published"], c["n_group"]
    by_group = np.repeat(_quantiles(groups, layer, 3000), n // groups)
    return np.exp(EXPERT_SIGMA * _quantiles(n, layer, 1000)
                  + GROUP_SIGMA * by_group)


def _ffn_weights(seed: int, c: dict, layer: int, dt, mat) -> dict:
    h = c["hidden_size"]
    if is_dense(c, layer):
        f = c["intermediate_size"]
        sg, su, sd = _swiglu_stds(h, f)
        return {"d_gate": mat("d_gate", (h, f), sg), "d_up": mat("d_up", (h, f), su),
                "d_down": mat("d_down", (f, h), sd).at[:, 0].set(0.0)}
    e, f = c["num_experts"], c["moe_intermediate_size"]
    g = c["moe_shared_expert_intermediate_size"]
    sg, su, sd = _swiglu_stds(h, f)
    # the routed sum's weights add up to routed_scaling_factor over all the
    # chosen experts; the shared expert adds SUBLAYER, the routed experts that
    # are held about as much again (an eighth of them: x 4)
    sd_routed = sd / c["routed_scaling_factor"] * 4
    gain = jnp.asarray(_router_gains(c, layer))
    router = (jax.random.normal(_key(seed, layer, "router"),
                                (h, c["experts_published"]), jnp.float32)
              * (ROUTER_SPREAD / math.sqrt(h)) * gain / jnp.mean(gain))
    # the stream's constant channel 0 as the router's offset: scores lie well
    # under 1/2, where a sigmoid is nearly an exponential, so the chosen
    # experts' weights fall off steeply and a pick that flips at the boundary
    # between two precisions moves little
    router = router.at[0, :].set(-ROUTER_SHIFT)
    w = {"router": router.astype(dt),
         "router_bias": jnp.asarray(
             BIAS_SPREAD * _quantiles(c["experts_published"], layer, 2000),
             jnp.float32),
         "e_gate": mat("e_gate", (e, h, f), sg), "e_up": mat("e_up", (e, h, f), su),
         "e_down": mat("e_down", (e, f, h), sd_routed).at[:, :, 0].set(0.0)}
    sg, su, sd = _swiglu_stds(h, g)
    w.update(s_gate=mat("s_gate", (h, g), sg), s_up=mat("s_up", (h, g), su),
             s_down=mat("s_down", (g, h), sd).at[:, 0].set(0.0))
    return w


def make_layer(seed: int, c: dict, layer: int, dtype) -> dict:
    """One layer's weights in ``dtype``, on the device, tensor by tensor."""
    kind = layer_kinds(c)[layer]
    h, heads = c["hidden_size"], c["num_attention_heads"]
    dt = jnp.dtype(dtype)
    unit = 1.0 / math.sqrt(h)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name), tuple(shape), std, dt)

    w = {"ln1": 1.0 + mat("ln1", (h,), 0.02), "ln2": 1.0 + mat("ln2", (h,), 0.02)}
    if kind == "mla":
        dn, dr, dv, rank = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                            c["v_head_dim"], c["kv_lora_rank"])
        # a score's spread is the product of a query lane's and a key lane's
        qk = MLA_SCORE_SPREAD ** 0.25
        w_dkv = jnp.concatenate([mat("w_dkv", (h, rank), unit),
                                 mat("wk", (h, dr), qk * unit)], axis=1)
        w_ukv = jnp.concatenate(
            [mat("w_ukv", (rank, heads, dn), qk / math.sqrt(rank)),
             mat("wv", (rank, heads, dv), 1.0 / math.sqrt(rank))], axis=2)
        w.update(wq=mat("wq", (h, heads, dn + dr), qk * unit), w_dkv=w_dkv,
                 kv_norm=1.0 + mat("kv_norm", (rank,), 0.02), w_ukv=w_ukv,
                 wg=mat("wg", (h, heads), unit),
                 wo=mat("wo", (heads, dv, h), MLA_OUT_GAIN * 2 * SUBLAYER
                        / math.sqrt(heads * dv)).at[:, :, 0].set(0.0))
    else:
        _, dk, taps = kda_shape(c)
        conv = mat("conv", (taps, 3, heads, dk), 0.3).at[-1].add(1.0)
        # a channel's decay a step, -g = d, log-uniform over DECAY_A_STEP: the
        # offset dt_bias puts the bounded gate's sigmoid at d / |lower bound|
        a_log = mat("a_log", (heads,), 0.3).astype(jnp.float32)
        lo, hi = (math.log(d) for d in DECAY_A_STEP)
        d = jnp.exp(jax.random.uniform(_key(seed, layer, "dt_bias"),
                                       (heads, dk), minval=lo, maxval=hi))
        share = d / abs(c["kda_lower_bound"])
        dt_bias = jnp.log(share / (1.0 - share)) / jnp.exp(a_log)[:, None]
        w.update(wq=mat("wq", (h, heads, dk), unit), wk=mat("wk", (h, heads, dk), unit),
                 wv=mat("wv", (h, heads, dk), unit), conv=conv,
                 wo=mat("wo", (heads, dk, h),
                        2 * SUBLAYER / math.sqrt(heads * dk)).at[:, :, 0].set(0.0),
                 wf=mat("wf", (h, heads, dk), DECAY_SWING * unit),
                 a_log=a_log, dt_bias=dt_bias.astype(jnp.float32),
                 wb=mat("wb", (h, heads), BETA_SPREAD * unit).at[0, :].set(-BETA_SHIFT),
                 wg=mat("wg", (h, heads, dk), unit),
                 norm=1.0 + mat("norm", (dk,), 0.02))
    w.update(_ffn_weights(seed, c, layer, dt, mat))
    return w


def make_ends(seed: int, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head over the held vocabulary."""
    h, v, dt = c["hidden_size"], c["vocab_size"], jnp.dtype(dtype)
    embed = _normal(_key(seed, -1, "embed"), (v, h), 1.0, dt).at[:, 0].set(1.0)
    head = _normal(_key(seed, -1, "head"), (h, v), 0.02, jnp.float32)
    # log-uniform by column: whatever the size of a program's error, some
    # twins lie closer than it and flip, and the farthest that flips reads it
    lo, hi = (math.log(s) for s in TWIN_SPREADS)
    spreads = jnp.exp(jax.random.uniform(_key(seed, -1, "twin_spreads"),
                                         (v // 2,), minval=lo, maxval=hi))
    twins = head[:, 0:2 * (v // 2):2] + spreads * _normal(
        _key(seed, -1, "twins"), (h, v // 2), 0.02, jnp.float32)
    head = head.at[:, 1:2 * (v // 2):2].set(twins).astype(dt)
    return {"embed": embed, "head": head,
            "norm_f": (1.0 + _normal(_key(seed, -1, "norm_f"), (h,), 0.02, dt)
                       ).astype(dt)}


def make_weights(seed: int, c: dict, dtype) -> dict:
    """All of it: ``{"embed", "head", "norm_f", "layers": [...]}``."""
    return dict(make_ends(seed, c, dtype),
                layers=[make_layer(seed, c, l, dtype)
                        for l in range(c["num_hidden_layers"])])


# -- the forward -----------------------------------------------------------------
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rotary(x, theta: float):
    """Rotary embedding over ALL lanes of ``x`` (T, ..., D), half-split lane
    order, position t the index on the first axis."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv).reshape(
        (t,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def routing(c: dict, m, router, bias):
    """(T, experts_published) weights, the five steps: sigmoid scores over ALL
    experts; the selection bias added; a group's score the sum of its two
    largest; the best ``topk_group`` groups stay, the rest masked out; the
    top-k of what remains chosen, weighed by the score alone, renormalised and
    scaled; zero elsewhere."""
    t, groups = m.shape[0], c["n_group"]
    s = jax.nn.sigmoid(m @ router)                                      # 1
    biased = s + bias                                                   # 2
    by_group = biased.reshape(t, groups, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], -1)            # 3
    _, kept = jax.lax.top_k(group_score, c["topk_group"])               # 4
    stays = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], kept].set(True)
    biased = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, -1)
    _, idx = jax.lax.top_k(biased, c["num_experts_per_tok"])            # 5
    top = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(top)


def picks_moved_by_rounding(c: dict, m, router, bias):
    """How many tokens' chosen experts change when the router's inputs are
    rounded to bfloat16 (products still summed in float32): a reading of how
    often a lower-precision program and this reference pick differently."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)     # noqa: E731
    return jnp.sum(jnp.any((routing(c, m, router, bias) > 0)
                           != (routing(c, low(m), low(router), bias) > 0), axis=-1))


def mla_row(c: dict, w: dict, a):
    """a (T, hidden) -> (T, kv_lora_rank + rope): what a latent layer knows of
    a position, ``[c ; k_r]``: the normed latent, and the ONE rotated key all
    heads share (the row a serving cache holds)."""
    rank = c["kv_lora_rank"]
    down = a @ w["w_dkv"]
    return jnp.concatenate(
        [_rms(down[:, :rank], w["kv_norm"], c["rms_norm_eps"]),
         _rotary(down[:, rank:], c["rope_theta"])], axis=-1)


def mla_mixer(c: dict, w: dict, a, rows: int = MLA_ROWS):
    """a (T, hidden), the normed input -> the latent attention half's output,
    EXPANDED: every position's keys and values up-projected from its latent,
    the masked score matrix computed ``rows`` query rows at a time."""
    t = a.shape[0]
    dn, dr, rank = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    q = jnp.einsum("th,hnd->tnd", a, w["wq"])
    row = mla_row(c, w, a)
    latent, k_r = row[:, :rank], row[:, rank:]
    q_n, q_r = q[..., :dn], _rotary(q[..., dn:], c["rope_theta"])
    kv = jnp.einsum("tr,rnd->tnd", latent, w["w_ukv"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    rows = min(rows, t)
    pad = -t % rows
    j = jnp.arange(t)[None, :]

    def block(x):
        qn, qr, i = x           # (rows, H, .), and the rows' positions
        s = (jnp.einsum("tnd,snd->nts", qn, k_n)
             + jnp.einsum("tnd,sd->nts", qr, k_r)) / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(j <= i[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nts,snd->tnd", p, v)

    blocks = lambda y: jnp.pad(                                     # noqa: E731
        y, ((0, pad),) + ((0, 0),) * (y.ndim - 1)).reshape((-1, rows) + y.shape[1:])
    o = jax.lax.map(block, (blocks(q_n), blocks(q_r), blocks(jnp.arange(t))))
    o = o.reshape((-1,) + o.shape[2:])[:t]
    o = o * jax.nn.sigmoid(a @ w["wg"])[..., None]          # a gate a head
    return jnp.einsum("tnd,ndh->th", o, w["wo"])


def kda_decay(c: dict, w: dict, a):
    """The bounded gate: (T, H, dk) in (kda_lower_bound, 0)."""
    f = jnp.einsum("th,hnd->tnd", a, w["wf"]) + w["dt_bias"]
    return c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None] * f)


def kda_recurrence(q, k, v, g, beta, state=None):
    """The literal recurrence over positions: q, k, g (T, H, dk), v (T, H,
    dv), beta (T, H) -> (o (T, H, dv), the last state (H, dk, dv))."""
    if state is None:
        state = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None]                       # diag(exp g) S
        S = S + (beta[:, None] * k)[..., None] * (
            v - jnp.einsum("hkv,hk->hv", S, k))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q) / math.sqrt(q.shape[-1])

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda_mixer(c: dict, w: dict, a):
    """a (T, hidden), the normed input -> the KDA half's output."""
    _, _, taps = kda_shape(c)
    t = a.shape[0]
    qkv = jnp.stack([jnp.einsum("th,hnd->tnd", a, w[n])
                     for n in ("wq", "wk", "wv")], axis=1)  # (T, 3, H, dk)
    padded = jnp.concatenate([jnp.zeros((taps - 1,) + qkv.shape[1:]), qkv])
    conv = sum(padded[j:j + t] * w["conv"][j] for j in range(taps))
    q, k, v = (jax.nn.silu(conv[:, i]) for i in range(3))
    q, k = _l2(q), _l2(k)
    beta = jax.nn.sigmoid(a @ w["wb"])
    o, _ = kda_recurrence(q, k, v, kda_decay(c, w, a), beta)
    o = _rms(o, w["norm"], c["rms_norm_eps"])
    o = o * jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", a, w["wg"]))
    return jnp.einsum("tnd,ndh->th", o, w["wo"])


def routed_half(c: dict, w: dict, m, experts=None):
    """m (T, hidden), the normed input -> (the held experts' part of the
    routed sum, the shared expert's output).  ``experts = (first, count)``
    names another share than the configuration's (the test that adds the
    shares up); ``w``'s expert matrices are that share's."""
    first, count = held_experts(c) if experts is None else experts
    weights = routing(c, m, w["router"], w["router_bias"])[:, first:first + count]

    def expert(y, ew):      # every token through expert e, weighted (0: not chosen)
        return y + ew[3][:, None] * _swiglu(m, ew[0], ew[1], ew[2]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (w["e_gate"], w["e_up"], w["e_down"], weights.T))
    return y, _swiglu(m, w["s_gate"], w["s_up"], w["s_down"])


def layer_forward(c: dict, layer: int, w: dict, x):
    """One layer on one sequence: x (T, hidden) float32 -> (the same, tokens
    whose picks rounding moves: :func:`picks_moved_by_rounding`, 0 on a dense
    layer)."""
    eps = c["rms_norm_eps"]
    a = _rms(x, w["ln1"], eps)
    mixer = mla_mixer if layer_kinds(c)[layer] == "mla" else kda_mixer
    x = x + mixer(c, w, a)
    m = _rms(x, w["ln2"], eps)
    if is_dense(c, layer):
        return x + _swiglu(m, w["d_gate"], w["d_up"], w["d_down"]), jnp.int32(0)
    routed, shared = routed_half(c, w, m)
    return (x + routed + shared,
            picks_moved_by_rounding(c, m, w["router"], w["router_bias"]))


def layer_forward_group(c: dict, layer: int, w: dict, xs):
    """:func:`layer_forward` on sequences of ONE length, ``xs`` (n, T,
    hidden): the same functions a sequence at a time (``lax.map``), but for
    the KDA mixer, whose literal scan over positions steps all n sequences
    together (``vmap``: a step is a few small products, and 36,864 of them a
    layer and sequence are most of a replay's time)."""
    eps = c["rms_norm_eps"]
    a = _rms(xs, w["ln1"], eps)
    if layer_kinds(c)[layer] == "mla":
        xs = xs + jax.lax.map(lambda y: mla_mixer(c, w, y), a)
    else:
        xs = xs + jax.vmap(lambda y: kda_mixer(c, w, y))(a)

    def ffn(x):
        m = _rms(x, w["ln2"], eps)
        if is_dense(c, layer):
            return x + _swiglu(m, w["d_gate"], w["d_up"], w["d_down"]), jnp.int32(0)
        routed, shared = routed_half(c, w, m)
        return (x + routed + shared,
                picks_moved_by_rounding(c, m, w["router"], w["router_bias"]))

    xs, moved = jax.lax.map(ffn, xs)
    return xs, jnp.sum(moved)


@jax.jit
def _logits_jit(ends, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ends["norm_f"], eps) @ ends["head"]


def forward_requests(seed: int, c: dict, dtype, requests: list, positions=None,
                     latent_at=None):
    """Logits float32 for each 0-based id sequence of ``requests``, every
    position against its whole causal context: layer by layer, one layer's
    float32 weights on the device at a time.  ``positions`` (a list of index
    arrays, one a request): the rows whose logits are wanted (all of them
    when None; a 32k prompt's own rows are not).  ``latent_at`` (the same
    form): the positions whose latent rows are wanted.  -> (the list of
    logits, the (token, layer) pairs whose picks rounding moves, of how many,
    and a request's :func:`mla_row` rows at ``latent_at``, (latent layers,
    positions, lanes), None without)."""
    f32 = lambda w: jax.tree_util.tree_map(         # noqa: E731
        lambda a: a.astype(jnp.float32), w)
    ends = f32(make_ends(seed, c, dtype))
    # requests of one (padded) length go through a layer together
    lengths = sorted({len(ids) for ids in requests})
    groups = [[i for i, ids in enumerate(requests) if len(ids) == n]
              for n in lengths]
    xs = [ends["embed"][jnp.asarray(np.stack([requests[i] for i in g]))]
          for g in groups]
    moved = 0
    latent = [[] for _ in requests]

    def run_layer(layer, w, x):
        with jax.default_matmul_precision("highest"):
            return layer_forward_group(c, layer, w, x)

    @jax.jit
    def rows_at(w, x, at):
        with jax.default_matmul_precision("highest"):
            return mla_row(c, w, _rms(x, w["ln1"], c["rms_norm_eps"]))[at]

    for layer in range(c["num_hidden_layers"]):
        w = f32(make_layer(seed, c, layer, dtype))
        if latent_at is not None and layer_kinds(c)[layer] == "mla":
            for g, x in zip(groups, xs):
                for j, i in enumerate(g):
                    latent[i].append(rows_at(w, x[j], jnp.asarray(latent_at[i])))
        fn = jax.jit(functools.partial(run_layer, layer))
        outs = [fn(w, x) for x in xs]
        xs = [o[0].block_until_ready() for o in outs]
        moved += sum(int(o[1]) for o in outs)
        del w, fn, outs
    routed = c["num_hidden_layers"] - c["first_k_dense_replace"]
    pairs = sum(x.shape[0] * x.shape[1] for x in xs) * routed
    rows = [None] * len(requests)
    for g, x in zip(groups, xs):
        for j, i in enumerate(g):
            rows[i] = x[j] if positions is None else x[j][jnp.asarray(positions[i])]
    return ([_logits_jit(ends, x, c["rms_norm_eps"]) for x in rows], moved, pairs,
            None if latent_at is None else [jnp.stack(r) for r in latent])


def forward(w: dict, c: dict, ids):
    """Logits (T, V) for one sequence from weights held whole (toy sizes)."""
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(w["embed"])[jnp.asarray(ids)]
        for layer, lw in enumerate(w["layers"]):
            x, _ = layer_forward(c, layer, jax.tree_util.tree_map(f32, lw), x)
        return _rms(x, f32(w["norm_f"]), c["rms_norm_eps"]) @ f32(w["head"])
