"""Plain Laguna-S-2.1 (poolside; the equations are written out from its public
``config.json``): seeded weights and the full causal forward in ``jax.numpy``,
float32, ``default_matmul_precision("highest")``.  No kernels, no cache, no
batching, nothing imported from the program.

What the configuration file says is what runs: ``num_hidden_layers`` layers of
the published pattern (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``), RMSNorm (eps 1e-6), 8 K/V heads of 128
shared by groups of query heads, rotate-half rotary (sliding layers the whole
head at theta 10,000; full layers the first half of each head with YaRN
frequencies over those dims, cos and sin times ``attention_factor``), a
sigmoid gate per head on the attention output, a dense SwiGLU MLP on the
``mlp_only_layers`` and, on the others, a softmax router over all
``experts_published`` experts, the ``num_experts_per_tok`` largest
renormalised and scaled by ``moe_routed_scaling_factor``, plus a shared
expert.  THE CHIP'S SHARE: of the routed sum only the experts held here are
added (``expert_share = [i, n]``: experts ``i * num_experts`` onward, of the
deployment's n holders), and the head is the held slice of the vocabulary;
what the absent experts would add is left out, in the program and here alike.
What the config does not say is under ``assumed`` in the configuration file.

``make_weights`` is the benchmark's weight maker, on the device from the seed
in the dtype the cell serves, one tensor at a time (a whole layer in float32
would not fit beside the rest); the reference makes them again LAYER BY LAYER
(:func:`make_layer`), upcasts (a bfloat16 value is exact in float32) and runs
every sampled request through a layer before it makes the next
(:func:`forward_requests`): the float32 copy of all five layers is 22 GB.

The seeded weights are chosen so that the check reads precision, as
``reference_gpt2`` explains for its own: odd head columns are near-twins of
their even neighbours, closer or farther by column over three decades (a
near-tie at every position; the twins closer than a program's error flip, so
the mean gap of the served tokens grows with the error and the widest reads
its size); hidden channel 0 is a
constant nothing writes to, and through it every K/V head carries an outlier
of ``KV_OUTLIER`` spreads in a key channel its query multiplies by exactly
zero and in a value channel the output projection drops (an absmax integer
KV format spends its range on them); attention scores are sharp (spread 3)
and every sublayer adds about half the residual's size, so that the cache
and the experts are a large part of every logit.  The router's columns have
uneven norms (spread ``ROUTER_SPREAD``, log-normal by expert, the same pattern
for every seed): routing is
decisive (the tenth pick's weight is a few hundredths of the first's, so a
pick that flips at the boundary between two precisions moves little) and
uneven (some experts draw many times the tokens of others).
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
ROUTER_SPREAD = 4.0         # of a router logit, mean over experts
SUBLAYER = 0.5              # what a sublayer adds, relative to a unit stream
DEAD_KEY, DEAD_VALUE = 0, 1     # channels of each K/V head, see the text above


# -- the configuration, by layer ---------------------------------------------
def layer_kinds(c: dict) -> list:
    """[(sliding?, query heads, sparse?)] for the layers that are run."""
    n = c["num_hidden_layers"]
    return [(c["layer_types"][l] == "sliding_attention",
             c["num_attention_heads_per_layer"][l],
             c["mlp_layer_types"][l] == "sparse") for l in range(n)]


def held_experts(c: dict):
    """(first, count) of the routed experts held here."""
    return c["expert_share"][0] * c["num_experts"], c["num_experts"]


def rope_tables(c: dict, sliding: bool, positions):
    """(cos, sin) (T, rot/2) and the number of rotated dims."""
    r = c["rope_parameters"]["sliding_attention" if sliding else "full_attention"]
    dim = int(c["head_dim"] * r["partial_rotary_factor"])
    pos_freqs = float(r["rope_theta"]) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv, scale = 1.0 / pos_freqs, 1.0
    if r["rope_type"] == "yarn":
        def correction(rotations):
            return (dim * math.log(r["original_max_position_embeddings"]
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(r["rope_theta"])))
        low = max(math.floor(correction(r["beta_fast"])), 0)
        high = min(math.ceil(correction(r["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / r["factor"] * ramp + inv * (1 - ramp)
        scale = r["attention_factor"]
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale, dim


# -- seeded weights ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _key(seed: int, layer: int, name: str):
    names = ("embed", "head", "twins", "twin_spreads", "norm_f", "ln1", "ln2", "wq", "wk", "wv",
             "wo", "wg", "w_gate", "w_up", "w_down", "router",
             "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), layer + 1)
    return jax.random.fold_in(key, names.index(name))


def _swiglu_stds(h: int, f: int):
    # gate and up of spread 1 from a unit input; silu(g) * u has a root mean
    # square of about 0.6, so the down projection's rows bring it to SUBLAYER
    return 1.0 / math.sqrt(h), 1.0 / math.sqrt(h), SUBLAYER / (0.6 * math.sqrt(f))


def _router_gains(n: int, layer: int) -> np.ndarray:
    """Log-normal column gains (sigma 0.5), the SAME for every seed: the
    normal's quantiles at the n mid-points in an order that depends on the
    layer alone.  How unevenly tokens spread over the experts, and how many
    land on the held ones, then does not change with the seed (a seed draws
    the directions), so a round's work does not either."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.exp(0.5 * np.random.RandomState(1000 + layer).permutation(z))


def make_layer(seed: int, c: dict, layer: int, dtype) -> dict:
    """One layer's weights in ``dtype``, on the device, tensor by tensor."""
    sliding, heads, sparse = layer_kinds(c)[layer]
    h, d, n_kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    dt = jnp.dtype(dtype)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name), tuple(shape), std, dt)

    qk = math.sqrt(SCORE_SPREAD / h)        # score spread = qk^2 * h (see text)
    if not sliding:                         # YaRN scales q and k both
        f = c["rope_parameters"]["full_attention"]["attention_factor"]
        qk /= f
    w = {"ln1": 1.0 + mat("ln1", (h,), 0.02), "ln2": 1.0 + mat("ln2", (h,), 0.02),
         "wq": mat("wq", (h, heads, d), qk), "wk": mat("wk", (h, n_kv, d), qk),
         "wv": mat("wv", (h, n_kv, d), 1.0 / math.sqrt(h)),
         "wo": mat("wo", (heads, d, h), 2 * SUBLAYER / math.sqrt(heads * d)),
         "wg": mat("wg", (h, heads), 1.0 / math.sqrt(h))}
    # channel 0 of the stream is a constant (make_ends) that no layer writes:
    # through it the dead key and value channels carry their outliers
    rot = int(d * c["rope_parameters"]["sliding_attention" if sliding
                                       else "full_attention"]["partial_rotary_factor"])
    dead = np.array([DEAD_KEY, DEAD_KEY + rot // 2])    # they rotate together
    spread_k, spread_v = qk * math.sqrt(h), 1.0
    w["wq"] = w["wq"].at[:, :, dead].set(0.0)
    w["wk"] = (w["wk"].at[:, :, dead].set(0.0)
               .at[0, :, DEAD_KEY].set(KV_OUTLIER * spread_k))
    w["wv"] = (w["wv"].at[:, :, DEAD_VALUE].set(0.0)
               .at[0, :, DEAD_VALUE].set(KV_OUTLIER * spread_v))
    w["wo"] = w["wo"].at[:, DEAD_VALUE, :].set(0.0).at[:, :, 0].set(0.0)
    if not sparse:
        f = c["intermediate_size"]
        sg, su, sd = _swiglu_stds(h, f)
        w.update(w_gate=mat("w_gate", (h, f), sg), w_up=mat("w_up", (h, f), su),
                 w_down=mat("w_down", (f, h), sd).at[:, 0].set(0.0))
        return w
    e, f, g = c["num_experts"], c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    sg, su, sd = _swiglu_stds(h, f)
    # the routed sum's weights add up to moe_routed_scaling_factor over all the
    # chosen experts; the shared expert adds SUBLAYER, the routed experts that
    # are held about as much again
    sd_routed = sd / c["moe_routed_scaling_factor"] * 2
    gain = jnp.asarray(_router_gains(c["experts_published"], layer))
    router = (jax.random.normal(_key(seed, layer, "router"),
                                (h, c["experts_published"]), jnp.float32)
              * (ROUTER_SPREAD / math.sqrt(h)) * gain / jnp.mean(gain))
    w.update(router=router.astype(dt),
             e_gate=mat("e_gate", (e, h, f), sg), e_up=mat("e_up", (e, h, f), su),
             e_down=mat("e_down", (e, f, h), sd_routed).at[:, :, 0].set(0.0))
    sg, su, sd = _swiglu_stds(h, g)
    w.update(s_gate=mat("s_gate", (h, g), sg), s_up=mat("s_up", (h, g), su),
             s_down=mat("s_down", (g, h), sd).at[:, 0].set(0.0))
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


def _rotate(x, cos, sin, dim):
    """x (T, H, D): rotate-half over the first ``dim`` dims of each head."""
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def routing(c: dict, m, router):
    """(T, experts_published) weights: the top-k of a softmax over ALL experts,
    renormalised and scaled; zero elsewhere."""
    probs = jax.nn.softmax(m @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["moe_routed_scaling_factor"]
    return jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None], idx].set(top)


def picks_moved_by_rounding(c: dict, m, router):
    """How many tokens' chosen experts change when the router's inputs are
    rounded to bfloat16 (products still summed in float32): a reading of how
    often a lower-precision program and this reference pick differently."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)     # noqa: E731
    return jnp.sum(jnp.any((routing(c, m, router) > 0)
                           != (routing(c, low(m), low(router)) > 0), axis=-1))


def layer_forward(c: dict, layer: int, w: dict, x):
    """One layer on one sequence: x (T, hidden) float32 -> (the same, tokens
    whose picks rounding moves: :func:`picks_moved_by_rounding`)."""
    sliding, heads, sparse = layer_kinds(c)[layer]
    t, d, n_kv, eps = x.shape[0], c["head_dim"], c["num_key_value_heads"], c["rms_norm_eps"]
    a = _rms(x, w["ln1"], eps)
    q = jnp.einsum("th,hnd->tnd", a, w["wq"])
    k = jnp.einsum("th,hnd->tnd", a, w["wk"])
    v = jnp.einsum("th,hnd->tnd", a, w["wv"])
    cos, sin, dim = rope_tables(c, sliding, np.arange(t))
    q, k = _rotate(q, cos, sin, dim), _rotate(k, cos, sin, dim)
    group = heads // n_kv                   # query head i reads K/V head i // group
    s = jnp.einsum("tngd,snd->ngts", q.reshape(t, n_kv, group, d), k) / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (j <= i) & ((i - j < c["sliding_window"]) if sliding else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("ngts,snd->tngd", p, v).reshape(t, heads, d)
    o = o * jax.nn.sigmoid(a @ w["wg"])[:, :, None]
    x = x + jnp.einsum("tnd,ndh->th", o, w["wo"])
    m = _rms(x, w["ln2"], eps)
    if not sparse:
        return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), 0
    first, count = held_experts(c)
    weights = routing(c, m, w["router"])[:, first:first + count]

    def expert(y, ew):      # every token through expert e, weighted (0: not chosen)
        return y + ew[3][:, None] * _swiglu(m, ew[0], ew[1], ew[2]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (w["e_gate"], w["e_up"], w["e_down"], weights.T))
    return (x + y + _swiglu(m, w["s_gate"], w["s_up"], w["s_down"]),
            picks_moved_by_rounding(c, m, w["router"]))


@jax.jit
def _logits_jit(ends, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ends["norm_f"], eps) @ ends["head"]


def forward_requests(seed: int, c: dict, dtype, requests: list):
    """Logits (T, V) float32 for each 0-based id sequence of ``requests``,
    every position against its whole causal context: layer by layer, one
    layer's float32 weights on the device at a time.  -> (the list of logits,
    the (token, sparse layer) pairs whose picks rounding moves, of how many)."""
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
    pairs = sum(x.shape[0] for x in xs) * sum(k[2] for k in layer_kinds(c))
    return [_logits_jit(ends, x, c["rms_norm_eps"]) for x in xs], moved, pairs


def forward(w: dict, c: dict, ids):
    """Logits (T, V) for one sequence from weights held whole (toy sizes)."""
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(w["embed"])[jnp.asarray(ids)]
        for layer, lw in enumerate(w["layers"]):
            x, _ = layer_forward(c, layer, jax.tree_util.tree_map(f32, lw), x)
        return _rms(x, f32(w["norm_f"]), c["rms_norm_eps"]) @ f32(w["head"])


