"""Plain MiMo-V2-Flash (XiaomiMiMo; the equations are written out from its
public ``config.json``): seeded weights and the full causal forward in
``jax.numpy``, float32, ``default_matmul_precision("highest")``.  No kernels,
no cache, no batching, nothing imported from the program.

What the configuration file says is what runs: ``num_hidden_layers`` layers of
the published patterns (``hybrid_layer_pattern``: 1 a SLIDING layer, 0 a FULL
one; ``moe_layer_freq``: 0 a dense feed-forward half, 1 a routed one), RMSNorm
(``layernorm_epsilon``), 64 query heads of 192 over 8 (sliding) or 4 (full)
K/V heads, values of 128 lanes scaled by ``attention_value_scale``, rotate-half
rotary over the first ``int(192 * partial_rotary_factor)`` = 64 lanes of a
head at ``swa_rope_theta`` / ``rope_theta``, a window of ``sliding_window``
positions that counts the query's own, and on a sliding layer the learned SINK
(``add_swa_attention_sink_bias``): one float32 logit a query head that joins
the softmax's denominator and owns no value.  The routed half: sigmoid scores
over all ``experts_published`` experts, the ``num_experts_per_tok`` largest of
score + selection bias, weighed by the score alone and renormalised
(``norm_topk_prob``), no groups, no shared expert, no scale.  THE CHIP'S
SHARE: of the routed sum only the experts held here are added
(``expert_share = [i, n]``: experts ``i * n_routed_experts`` onward, of the
deployment's n holders), and the head is the held slice of the vocabulary.
What the config does not say is under ``assumed`` in the configuration file.

Departures from the literal equations, none of which changes a value: the
score matrix is made a BLOCK OF QUERY ROWS at a time (``ROWS``: 41,000
positions x 64 heads of float32 scores do not fit whole), a sliding layer's
block against the keys its rows' windows reach, a full layer's against every
key under the causal mask; the weights are made and upcast LAYER BY LAYER
(:func:`forward_requests`), as ``reference_laguna`` does.

The seeded weights are ``reference_laguna``'s and ``reference_solar2``'s
(unit-size stream with a constant channel 0, near-twin head columns, outliers
in dead K/V channels, a sigmoid router whose scores lie well under 1/2), with
two of their own: the router's column gains are NEARLY EVEN
(``EXPERT_SIGMA``), the same for every seed, so that the held experts' share of
a round's tokens does not move with the seed; and the SINKS MATTER: a sliding
layer's are drawn (``SINK_RANGE``) about the log of the summed exponentials of
a full window's scores, so that the sink takes between a tenth and a half of a
row's probability -- a program that leaves it out, or lets a row see a block
it has let go of, then reads far outside the check's limits.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference_gpt2 import gaps_below_best  # noqa: F401  (the check's reading, shared)
from benchmarks.harness.reference_solar2 import _quantiles

TWIN_SPREADS = (1e-4, 1e-1)  # of a twin column about its neighbour, in spreads
KV_OUTLIER = 200.0          # dead key / value channels, in spreads of a live one
SCORE_SPREAD = 3.0          # variance of q.k / sqrt(d) before the softmax
ROUTER_SPREAD = 2.0         # of a router logit, mean over experts
ROUTER_SHIFT = 8.0          # what the constant stream channel takes off every logit
BIAS_SPREAD = 0.005         # of the router's selection bias
EXPERT_SIGMA = 0.1          # log-normal spread of the router's column gains
SUBLAYER = 0.5              # what a sublayer adds, relative to a unit stream
DEAD_KEY, DEAD_VALUE = 0, 1     # channels of each K/V head, see reference_laguna
#: a sink's logit less log(sum of exp) of a full window's scores (log(window)
#: + SCORE_SPREAD / 2): log-odds of a tenth .. a half of a row's probability
SINK_RANGE = (-2.0, -0.1)
#: query rows a block of the score matrix holds
ROWS = 128


# -- the configuration, by layer ---------------------------------------------
def layer_kinds(c: dict) -> list:
    """[(sliding?, routed?)] for the layers that are run."""
    return [(bool(c["hybrid_layer_pattern"][l]), bool(c["moe_layer_freq"][l]))
            for l in range(c["num_hidden_layers"])]


def kv_heads(c: dict, sliding: bool) -> int:
    return c["swa_num_key_value_heads" if sliding else "num_key_value_heads"]


def has_sink(c: dict, sliding: bool) -> bool:
    return bool(c["add_swa_attention_sink_bias" if sliding
                  else "add_full_attention_sink_bias"])


def rotary_dim(c: dict) -> int:
    return int(c["head_dim"] * c["partial_rotary_factor"])


def held_experts(c: dict):
    """(first, count) of the routed experts held here."""
    return c["expert_share"][0] * c["n_routed_experts"], c["n_routed_experts"]


def rope_tables(c: dict, sliding: bool, positions):
    """(cos, sin) (T, rot / 2) and the number of rotated dims."""
    dim = rotary_dim(c)
    theta = float(c["swa_rope_theta" if sliding else "rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang), dim


# -- seeded weights ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _key(seed: int, layer: int, name: str, share: int = 0):
    names = ("embed", "head", "twins", "twin_spreads", "norm_f", "ln1", "ln2",
             "wq", "wk", "wv", "wo", "sink", "w_gate", "w_up", "w_down",
             "router", "e_gate", "e_up", "e_down")
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), layer + 1)
    key = jax.random.fold_in(key, names.index(name))
    return jax.random.fold_in(key, share) if share else key


def _swiglu_stds(h: int, f: int):
    # gate and up of spread 1 from a unit input; silu(g) * u has a root mean
    # square of about 0.6, so the down projection's rows bring it to SUBLAYER
    return 1.0 / math.sqrt(h), 1.0 / math.sqrt(h), SUBLAYER / (0.6 * math.sqrt(f))


def make_experts(seed: int, c: dict, layer: int, dtype, share: int) -> dict:
    """The ``n_routed_experts`` experts of ONE share of the deployment (share
    i holds experts ``i * n_routed_experts`` onward)."""
    h, e, f = c["hidden_size"], c["n_routed_experts"], c["moe_intermediate_size"]
    dt = jnp.dtype(dtype)
    sg, su, sd = _swiglu_stds(h, f)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name, share), tuple(shape), std, dt)

    # the chosen experts' weights add up to 1 and a sixteenth of them is
    # held: the held experts add about SUBLAYER where one is chosen
    return {"e_gate": mat("e_gate", (e, h, f), sg), "e_up": mat("e_up", (e, h, f), su),
            "e_down": mat("e_down", (e, f, h), 4 * sd).at[:, :, 0].set(0.0)}


def make_layer(seed: int, c: dict, layer: int, dtype) -> dict:
    """One layer's weights in ``dtype``, on the device, tensor by tensor."""
    sliding, routed = layer_kinds(c)[layer]
    h, d, dv = c["hidden_size"], c["head_dim"], c["v_head_dim"]
    heads, n_kv = c["num_attention_heads"], kv_heads(c, sliding)
    dt = jnp.dtype(dtype)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name), tuple(shape), std, dt)

    qk = math.sqrt(SCORE_SPREAD / h)        # score variance = qk^2 * h
    w = {"ln1": 1.0 + mat("ln1", (h,), 0.02), "ln2": 1.0 + mat("ln2", (h,), 0.02),
         "wq": mat("wq", (h, heads, d), qk), "wk": mat("wk", (h, n_kv, d), qk),
         # the values are scaled before they are cached: spread 1 after it
         "wv": mat("wv", (h, n_kv, dv),
                   1.0 / (math.sqrt(h) * c["attention_value_scale"])),
         "wo": mat("wo", (heads, dv, h), 2 * SUBLAYER / math.sqrt(heads * dv))}
    # channel 0 of the stream is a constant (make_ends) that no layer writes:
    # through it the dead key and value channels carry their outliers
    dead = np.array([DEAD_KEY, DEAD_KEY + rotary_dim(c) // 2])  # rotate together
    w["wq"] = w["wq"].at[:, :, dead].set(0.0)
    w["wk"] = (w["wk"].at[:, :, dead].set(0.0)
               .at[0, :, DEAD_KEY].set(KV_OUTLIER * qk * math.sqrt(h)))
    w["wv"] = (w["wv"].at[:, :, DEAD_VALUE].set(0.0)
               .at[0, :, DEAD_VALUE].set(KV_OUTLIER / c["attention_value_scale"]))
    w["wo"] = w["wo"].at[:, DEAD_VALUE, :].set(0.0).at[:, :, 0].set(0.0)
    if has_sink(c, sliding):
        # float32 whatever the serving dtype (assumed): about the log of a
        # full window's summed exponentials, so that it takes a tenth to a
        # half of a row's probability
        lse = math.log(c["sliding_window"]) + SCORE_SPREAD / 2
        w["sink"] = lse + jax.random.uniform(
            _key(seed, layer, "sink"), (heads,), jnp.float32, *SINK_RANGE)
    if not routed:
        f = c["intermediate_size"]
        sg, su, sd = _swiglu_stds(h, f)
        w.update(w_gate=mat("w_gate", (h, f), sg), w_up=mat("w_up", (h, f), su),
                 w_down=mat("w_down", (f, h), sd).at[:, 0].set(0.0))
        return w
    n = c["experts_published"]
    gain = jnp.asarray(np.exp(EXPERT_SIGMA * _quantiles(n, layer, 1000)))
    router = (jax.random.normal(_key(seed, layer, "router"), (h, n), jnp.float32)
              * (ROUTER_SPREAD / math.sqrt(h)) * gain / jnp.mean(gain))
    # the stream's constant channel 0 as the router's offset: scores lie well
    # under 1/2, where a sigmoid is nearly an exponential (reference_solar2)
    router = router.at[0, :].set(-ROUTER_SHIFT)
    w.update(router=router.astype(dt),
             router_bias=jnp.asarray(BIAS_SPREAD * _quantiles(n, layer, 2000),
                                     jnp.float32),
             **make_experts(seed, c, layer, dtype, c["expert_share"][0]))
    return w


def make_ends(seed: int, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head over the held vocabulary."""
    h, v, dt = c["hidden_size"], c["vocab_size"], jnp.dtype(dtype)
    embed = _normal(_key(seed, -1, "embed"), (v, h), 1.0, dt).at[:, 0].set(1.0)
    head = _normal(_key(seed, -1, "head"), (h, v), 0.02, jnp.float32)
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


def routing(c: dict, m, router, bias):
    """(T, experts_published) weights: sigmoid scores over ALL experts, the
    top-k of score + bias chosen, weighed by the score alone and
    renormalised; zero elsewhere."""
    s = jax.nn.sigmoid(m @ router)
    _, idx = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], idx].set(top)


def picks_moved_by_rounding(c: dict, m, router, bias):
    """How many tokens' chosen experts change when the router's inputs are
    rounded to bfloat16 (``reference_solar2``)."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)     # noqa: E731
    return jnp.sum(jnp.any((routing(c, m, router, bias) > 0)
                           != (routing(c, low(m), low(router), bias) > 0), axis=-1))


def attention(c: dict, sliding: bool, q, k, v, sink):
    """q (T, H, 192) and k (T, H_kv, 192) rotated, v (T, H_kv, 128) scaled,
    ``sink`` (H,) or None -> o (T, H, 128): the softmax a block of ``ROWS``
    query rows at a time, a sliding layer's block against the keys its rows'
    windows reach, a full layer's against every key."""
    t, heads, d = q.shape
    n_kv, window = k.shape[1], c["sliding_window"]
    rows = min(ROWS, t)
    blocks = -(-t // rows)
    pad = blocks * rows - t
    # a sliding block's keys: positions first - (window - 1) .. first + rows - 1
    lead = window - 1 if sliding else 0
    span = rows + lead if sliding else blocks * rows
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, rows, n_kv, heads // n_kv, d)
    k = jnp.pad(k, ((lead, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((lead, pad), (0, 0), (0, 0)))
    sink = None if sink is None else sink.reshape(n_kv, heads // n_kv, 1)

    def block(x):
        qb, first = x
        at = first if sliding else 0
        kb = jax.lax.dynamic_slice_in_dim(k, at, span)
        vb = jax.lax.dynamic_slice_in_dim(v, at, span)
        i = (first + jnp.arange(rows))[:, None]
        j = (at - lead + jnp.arange(span))[None, :]
        seen = (j >= 0) & (j <= i) & ((i - j < window) if sliding else True)
        s = jnp.einsum("tngd,snd->ngts", qb, kb) / math.sqrt(d)
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, -1)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(s - top[..., None])
        den = jnp.sum(e, -1)
        if sink is not None:    # the sink takes probability and gives no value
            den = den + jnp.exp(sink - top)
        return jnp.einsum("ngts,snd->tngd", e / den[..., None], vb)

    o = jax.lax.map(block, (q, jnp.arange(blocks) * rows))
    return o.reshape(blocks * rows, heads, v.shape[-1])[:t]


def routed_part(c: dict, m, weights, experts: dict):
    """The routed sum of ONE share: ``weights`` (T, its experts) -- zero where
    an expert was not chosen -- over that share's ``experts``."""
    def expert(y, ew):      # every token through expert e, weighted
        return y + ew[3][:, None] * _swiglu(m, ew[0], ew[1], ew[2]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (experts["e_gate"], experts["e_up"], experts["e_down"],
                         weights.T))
    return y


def layer_forward(c: dict, layer: int, w: dict, x):
    """One layer on one sequence: x (T, hidden) float32 -> (the same, tokens
    whose picks rounding moves)."""
    sliding, routed = layer_kinds(c)[layer]
    t, eps = x.shape[0], c["layernorm_epsilon"]
    a = _rms(x, w["ln1"], eps)
    q = jnp.einsum("th,hnd->tnd", a, w["wq"])
    k = jnp.einsum("th,hnd->tnd", a, w["wk"])
    v = c["attention_value_scale"] * jnp.einsum("th,hnd->tnd", a, w["wv"])
    cos, sin, dim = rope_tables(c, sliding, np.arange(t))
    q, k = _rotate(q, cos, sin, dim), _rotate(k, cos, sin, dim)
    o = attention(c, sliding, q, k, v, w.get("sink"))
    x = x + jnp.einsum("tnd,ndh->th", o, w["wo"])
    m = _rms(x, w["ln2"], eps)
    if not routed:
        return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), 0
    first, count = held_experts(c)
    weights = routing(c, m, w["router"], w["router_bias"])[:, first:first + count]
    return (x + routed_part(c, m, weights, w),
            picks_moved_by_rounding(c, m, w["router"], w["router_bias"]))


@jax.jit
def _logits_jit(ends, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ends["norm_f"], eps) @ ends["head"]


def forward_requests(seed: int, c: dict, dtype, requests: list, rows=None):
    """Logits float32 for each 0-based id sequence of ``requests``, every
    position against its whole causal context: layer by layer, one layer's
    float32 weights on the device at a time.  ``rows``: for each request the
    positions whose logits are wanted (None: all).  -> (the list of logits,
    the (token, routed layer) pairs whose picks rounding moves, of how
    many)."""
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
    pairs = sum(x.shape[0] for x in xs) * sum(k[1] for k in layer_kinds(c))
    if rows is not None:
        xs = [x[jnp.asarray(r)] for x, r in zip(xs, rows)]
    return ([_logits_jit(ends, x, c["layernorm_epsilon"]) for x in xs], moved,
            pairs)


def forward(w: dict, c: dict, ids):
    """Logits (T, V) for one sequence from weights held whole (toy sizes)."""
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(w["embed"])[jnp.asarray(ids)]
        for layer, lw in enumerate(w["layers"]):
            x, _ = layer_forward(c, layer, jax.tree_util.tree_map(f32, lw), x)
        return _rms(x, f32(w["norm_f"]), c["layernorm_epsilon"]) @ f32(w["head"])
