"""Plain Solar-Open2-250B (upstage; the equations are written out from its
public ``config.json`` and, where that is silent, from the Kimi-Linear form of
KDA: every such choice is under ``assumed`` in the configuration file): seeded
weights and the full causal forward in ``jax.numpy``, float32,
``default_matmul_precision("highest")``.  No kernels, no cache, no chunks, no
batching, nothing imported from the program.

What the configuration file says is what runs: ``num_hidden_layers`` layers,
layer l a softmax layer iff l is in ``gqa_layers`` (every fourth), else a KDA
layer; RMSNorm (eps 1e-5), pre-norm residual; every layer's feed-forward half
routed.

*Softmax layer*: 64 query heads of 128 over 8 K/V heads, NO position encoding
(``use_rope`` false), causal softmax, an ELEMENTWISE sigmoid gate read from
the normed input on the attention output (``use_gqa_gate``).

*KDA layer* (gated delta rule with a per-channel decay), THE LITERAL
RECURRENCE, a ``lax.scan`` over positions, a head's state S in R^{128 x 128}::

    q_t, k_t = l2norm(silu(conv4(a Wq)))_t, l2norm(silu(conv4(a Wk)))_t
    v_t = silu(conv4(a Wv))_t                 (depthwise, causal, 4 taps)
    g_t = -exp(A_log_h) * softplus((a Wf1 Wf2)_t + dt_bias)     in R^128
    beta_t = 2 * sigmoid((a Wb)_t)
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(128)
    out = (rmsnorm_head(o_t) * sigmoid((a Wg1 Wg2)_t)) Wo

*Routed half*: ``s = sigmoid(x Wr)`` over all ``experts_published`` experts in
float32, the ``num_experts_per_tok`` largest of ``s + bias`` chosen, weighed
by the UNBIASED ``s`` renormalised (``norm_topk_prob``) times
``routed_scaling_factor``, plus one shared expert.  THE CHIP'S SHARE: of the
routed sum only the experts held here are added (``expert_share = [i, n]``:
experts ``i * n_routed_experts`` onward, of the deployment's n holders), and
the head is the held slice of the vocabulary; what the absent experts would
add is left out, in the program and here alike.

The seeded weights are chosen so that the check reads precision, as
``reference_laguna`` explains for its own (near-twin head columns over three
decades, a constant stream channel 0 that carries outliers into a dead key
and a dead value channel of the softmax layers, sharp scores, half a stream a
sublayer, a router of uneven column norms that is the same for every seed and
whose scores lie well under 1/2: ``ROUTER_SHIFT``, so that routing is decisive
as Laguna's softmax router's is).
The recurrent layers add: decays spread LOG-UNIFORMLY by channel, so that some
channels forget in 4 steps and some in 4,000 (``DECAY_STEPS``); betas on both
sides of 1 that a head seldom raises (``BETA_SPREAD``, ``BETA_SHIFT``: it
writes at about one position in twelve, above 1 at one in twenty-five, and
otherwise HOLDS, beta under 1e-4); a convolution whose newest tap is the
largest.  Between a head's writes a slow channel's decay, 0.025% to 0.3% a
step, is all that moves its state: float32 keeps it, and a state kept in
bfloat16 (half a unit in the last place is 0.2-0.4%) rounds back to where it
was step after step and never forgets, so what it hands a query drifts from
the reference's by a share that grows with the decode steps since the
prefill (the chunked form carries float32 inside a call).  With betas about
1 at every position each step's write is a dither under which the same
rounding averages out, and a bfloat16 state read as sound on the chip (PERF.md,
Findings, PR 33).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference_gpt2 import gaps_below_best  # noqa: F401  (the check's reading, shared)

TWIN_SPREADS = (1e-4, 1e-1)  # of a twin column about its neighbour, in spreads
KV_OUTLIER = 200.0          # dead key / value channels, in spreads of a live one
SCORE_SPREAD = 3.0          # of q.k / sqrt(d) before the softmax
ROUTER_SPREAD = 2.0         # of a router logit, mean over experts
ROUTER_SHIFT = 8.0          # what the constant stream channel takes off every logit
BIAS_SPREAD = 0.005         # of the router's selection bias
SUBLAYER = 0.5              # what a sublayer adds, relative to a unit stream
DEAD_KEY, DEAD_VALUE = 0, 1     # channels of each K/V head, see reference_laguna
DECAY_STEPS = (4.0, 4000.0)     # steps in which a channel forgets, log-uniform
DECAY_SWING = 0.5           # spread of the decay's data-dependent logit
BETA_SPREAD = 8.0           # of beta's logit (beta = 2 sigmoid of it)
BETA_SHIFT = 14.0           # what the constant stream channel takes off beta's logit


# -- the configuration, by layer ---------------------------------------------
def layer_kinds(c: dict) -> list:
    """["softmax" | "kda"] for the layers that are run."""
    return ["softmax" if l in c["gqa_layers"] else "kda"
            for l in range(c["num_hidden_layers"])]


def held_experts(c: dict):
    """(first, count) of the routed experts held here."""
    return c["expert_share"][0] * c["n_routed_experts"], c["n_routed_experts"]


def kda_shape(c: dict):
    """(heads, head_dim, conv taps) of a KDA layer."""
    la = c["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


# -- seeded weights ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


_NAMES = ("embed", "head", "twins", "twin_spreads", "norm_f", "ln1", "ln2",
          "wq", "wk", "wv", "wo", "wg", "router", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down", "conv", "wf1", "wf2", "a_log", "dt_bias",
          "wb", "wg1", "wg2", "norm")


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


def _router_gains(n: int, layer: int) -> np.ndarray:
    """Log-normal column gains (sigma 0.5), the same for every seed: how
    unevenly tokens spread over the experts, and how many land on the held
    ones, does not change with the seed (a seed draws the directions)."""
    return np.exp(0.5 * _quantiles(n, layer, 1000))


def _moe_weights(seed: int, c: dict, layer: int, dt, mat) -> dict:
    h, e, f = c["hidden_size"], c["n_routed_experts"], c["moe_intermediate_size"]
    g = f * c["n_shared_experts"]
    sg, su, sd = _swiglu_stds(h, f)
    # the routed sum's weights add up to routed_scaling_factor over all the
    # chosen experts; the shared expert adds SUBLAYER, the routed experts that
    # are held about as much again (an eighth of them: x 4)
    sd_routed = sd / c["routed_scaling_factor"] * 4
    gain = jnp.asarray(_router_gains(c["experts_published"], layer))
    router = (jax.random.normal(_key(seed, layer, "router"),
                                (h, c["experts_published"]), jnp.float32)
              * (ROUTER_SPREAD / math.sqrt(h)) * gain / jnp.mean(gain))
    # the stream's constant channel 0 as the router's offset: scores lie well
    # under 1/2, where a sigmoid is nearly an exponential, so the chosen
    # experts' weights fall off steeply (the eighth a few hundredths) and a
    # pick that flips at the boundary between two precisions moves little
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
    h, d = c["hidden_size"], c["head_dim"]
    dt = jnp.dtype(dtype)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name), tuple(shape), std, dt)

    w = {"ln1": 1.0 + mat("ln1", (h,), 0.02), "ln2": 1.0 + mat("ln2", (h,), 0.02)}
    if kind == "softmax":
        heads, n_kv = c["num_attention_heads"], c["num_key_value_heads"]
        qk = math.sqrt(SCORE_SPREAD / h)    # score spread = qk^2 * h
        w.update(wq=mat("wq", (h, heads, d), qk), wk=mat("wk", (h, n_kv, d), qk),
                 wv=mat("wv", (h, n_kv, d), 1.0 / math.sqrt(h)),
                 wo=mat("wo", (heads, d, h), 2 * SUBLAYER / math.sqrt(heads * d)),
                 wg=mat("wg", (h, heads, d), 1.0 / math.sqrt(h)))
        # channel 0 of the stream is a constant (make_ends) that no layer
        # writes: through it the dead key and value channels carry outliers
        w["wq"] = w["wq"].at[:, :, DEAD_KEY].set(0.0)
        w["wk"] = (w["wk"].at[:, :, DEAD_KEY].set(0.0)
                   .at[0, :, DEAD_KEY].set(KV_OUTLIER * qk * math.sqrt(h)))
        w["wv"] = (w["wv"].at[:, :, DEAD_VALUE].set(0.0)
                   .at[0, :, DEAD_VALUE].set(KV_OUTLIER))
        w["wo"] = w["wo"].at[:, DEAD_VALUE, :].set(0.0).at[:, :, 0].set(0.0)
    else:
        heads, dk, taps = kda_shape(c)
        unit = 1.0 / math.sqrt(h)
        conv = mat("conv", (taps, 3, heads, dk), 0.3).at[-1].add(1.0)
        # a channel forgets in n steps when its decay is exp(-1/n) a step:
        # the offset dt_bias puts softplus at 1/n, n log-uniform by channel
        lo, hi = (math.log(1.0 / n) for n in DECAY_STEPS)
        rate = jnp.exp(jax.random.uniform(_key(seed, layer, "dt_bias"),
                                          (heads, dk), minval=hi, maxval=lo))
        w.update(wq=mat("wq", (h, heads, dk), unit), wk=mat("wk", (h, heads, dk), unit),
                 wv=mat("wv", (h, heads, dk), unit), conv=conv,
                 wo=mat("wo", (heads, dk, h),
                        2 * SUBLAYER / math.sqrt(heads * dk)).at[:, :, 0].set(0.0),
                 wf1=mat("wf1", (h, dk), unit),
                 wf2=mat("wf2", (dk, heads, dk), DECAY_SWING / math.sqrt(dk)),
                 a_log=mat("a_log", (heads,), 0.3).astype(jnp.float32),
                 dt_bias=jnp.log(jnp.expm1(rate)).astype(jnp.float32),
                 wb=mat("wb", (h, heads), BETA_SPREAD * unit).at[0, :].set(-BETA_SHIFT),
                 wg1=mat("wg1", (h, dk), unit),
                 wg2=mat("wg2", (dk, heads, dk), 1.0 / math.sqrt(dk)),
                 norm=1.0 + mat("norm", (dk,), 0.02))
    w.update(_moe_weights(seed, c, layer, dt, mat))
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


def routing(c: dict, m, router, bias):
    """(T, experts_published) weights: sigmoid scores over ALL experts, the
    top-k of score + bias chosen, weighed by the score alone, renormalised
    and scaled; zero elsewhere."""
    s = jax.nn.sigmoid(m @ router)
    _, idx = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], idx].set(top)


def picks_moved_by_rounding(c: dict, m, router, bias):
    """How many tokens' chosen experts change when the router's inputs are
    rounded to bfloat16 (products still summed in float32): a reading of how
    often a lower-precision program and this reference pick differently."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)     # noqa: E731
    return jnp.sum(jnp.any((routing(c, m, router, bias) > 0)
                           != (routing(c, low(m), low(router), bias) > 0), axis=-1))


def softmax_mixer(c: dict, w: dict, a):
    """a (T, hidden), the normed input -> the attention half's output."""
    t, d = a.shape[0], c["head_dim"]
    heads, n_kv = c["num_attention_heads"], c["num_key_value_heads"]
    q = jnp.einsum("th,hnd->tnd", a, w["wq"])
    k = jnp.einsum("th,hnd->tnd", a, w["wk"])
    v = jnp.einsum("th,hnd->tnd", a, w["wv"])
    group = heads // n_kv                   # query head i reads K/V head i // group
    s = jnp.einsum("tngd,snd->ngts", q.reshape(t, n_kv, group, d), k) / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    o = jnp.einsum("ngts,snd->tngd", p, v).reshape(t, heads, d)
    o = o * jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", a, w["wg"]))
    return jnp.einsum("tnd,ndh->th", o, w["wo"])


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
    heads, dk, taps = kda_shape(c)
    t = a.shape[0]
    qkv = jnp.stack([jnp.einsum("th,hnd->tnd", a, w[n])
                     for n in ("wq", "wk", "wv")], axis=1)  # (T, 3, H, dk)
    padded = jnp.concatenate([jnp.zeros((taps - 1,) + qkv.shape[1:]), qkv])
    conv = sum(padded[j:j + t] * w["conv"][j] for j in range(taps))
    q, k, v = (jax.nn.silu(conv[:, i]) for i in range(3))
    q, k = _l2(q), _l2(k)
    f = jnp.einsum("tr,rnd->tnd", a @ w["wf1"], w["wf2"]) + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(f)
    beta = 2.0 * jax.nn.sigmoid(a @ w["wb"])
    o, _ = kda_recurrence(q, k, v, g, beta)
    o = _rms(o, w["norm"], c["rms_norm_eps"])
    o = o * jax.nn.sigmoid(jnp.einsum("tr,rnd->tnd", a @ w["wg1"], w["wg2"]))
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
    whose picks rounding moves: :func:`picks_moved_by_rounding`)."""
    eps = c["rms_norm_eps"]
    a = _rms(x, w["ln1"], eps)
    mixer = softmax_mixer if layer_kinds(c)[layer] == "softmax" else kda_mixer
    x = x + mixer(c, w, a)
    m = _rms(x, w["ln2"], eps)
    routed, shared = routed_half(c, w, m)
    return (x + routed + shared,
            picks_moved_by_rounding(c, m, w["router"], w["router_bias"]))


@jax.jit
def _logits_jit(ends, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ends["norm_f"], eps) @ ends["head"]


def forward_requests(seed: int, c: dict, dtype, requests: list):
    """Logits (T, V) float32 for each 0-based id sequence of ``requests``,
    every position against its whole causal context: layer by layer, one
    layer's float32 weights on the device at a time.  -> (the list of logits,
    the (token, layer) pairs whose picks rounding moves, of how many)."""
    f32 = lambda w: jax.tree_util.tree_map(         # noqa: E731
        lambda a: a.astype(jnp.float32), w)
    ends = f32(make_ends(seed, c, dtype))
    xs = [ends["embed"][jnp.asarray(ids)] for ids in requests]
    moved = 0

    def run_layer(layer, w, x):
        with jax.default_matmul_precision("highest"):
            return layer_forward(c, layer, w, x)

    for layer in range(c["num_hidden_layers"]):
        w = f32(make_layer(seed, c, layer, dtype))
        fn = jax.jit(functools.partial(run_layer, layer))
        outs = [fn(w, x) for x in xs]
        xs = [o[0].block_until_ready() for o in outs]
        moved += sum(int(o[1]) for o in outs)
        del w, fn, outs
    pairs = sum(x.shape[0] for x in xs) * c["num_hidden_layers"]
    return [_logits_jit(ends, x, c["rms_norm_eps"]) for x in xs], moved, pairs


def forward(w: dict, c: dict, ids):
    """Logits (T, V) for one sequence from weights held whole (toy sizes)."""
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(w["embed"])[jnp.asarray(ids)]
        for layer, lw in enumerate(w["layers"]):
            x, _ = layer_forward(c, layer, jax.tree_util.tree_map(f32, lw), x)
        return _rms(x, f32(w["norm_f"]), c["rms_norm_eps"]) @ f32(w["head"])
