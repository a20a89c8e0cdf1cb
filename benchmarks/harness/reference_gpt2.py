"""Plain GPT-2 (Radford et al. 2019): seeded weights and the full causal
forward in ``jax.numpy``, float32, ``default_matmul_precision("highest")``.
No kernels, no cache, no batching, nothing imported from the program.

Pre-LayerNorm blocks (eps 1e-5), learned positions, tanh-GELU MLP of width
4h, biases everywhere, the head tied to the token embedding.  Layer weights
are stacked on a leading layer axis and the forward scans over it.

``make_weights`` is the benchmark's weight maker: one jitted call on the
device from the seed, in the dtype the cell serves.  The driver hands these
arrays to the program; the reference makes them again from the seed and
upcasts (a bfloat16 value is exact in float32), so the two sides hold the same
numbers and neither takes anything the other made.

The seeded weights are hard on a quantizer the way trained ones are, and
Gaussian ones are not (``_stress``): every matrix a block multiplies by, the
keys and the values carry an outlier in a channel that exact arithmetic
never reads (its multiplier is exactly zero in any floating-point type).
A floating-point program cannot tell; an absmax integer format (the
program's ``quantize()``, per output column, and ``kv_quant="int8"``, per
position and head) spends its range on the outlier and rounds the rest
away.  The attention output's projection is scaled up (``ATTN_GAIN``) so
that what attention reads from the cache is as large a part of the residual
stream as the MLP's: at a plain seeded init it is a hundredth of it, and no
check on the output can see the cache at all.  The check's control is the
program itself with those two paths switched on (tests/readings.py
--control; PERF.md section 2 has the readings).
"""
import functools

import jax
import jax.numpy as jnp

#: spread of a twin row about its neighbour, as a share of the init's 0.02
TWIN_SPREAD = 0.002

#: outliers, in units of their tensor's spread: in every block matrix (a dead
#: input row) and in every head's keys and values (a dead channel)
WEIGHT_OUTLIER, KV_OUTLIER = 100.0, 200.0
#: the attention output projection's spread over the MLP projection's
ATTN_GAIN = 16.0

#: the per-layer leaves, stacked on a leading layer axis
_LAYER = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
          "ln2_g", "ln2_b", "w_fc", "b_fc", "w_proj", "b_proj")


def _shapes(c: dict) -> dict:
    h, f, n = c["n_embd"], 4 * c["n_embd"], c["n_layer"]
    s = {"wte": (c["vocab_size"], h), "wpe": (c["n_positions"], h),
         "ln_f_g": (h,), "ln_f_b": (h,)}
    for name in _LAYER:
        if name.startswith("ln") or name in ("bq", "bk", "bv", "bo", "b_proj"):
            s[name] = (n, h)
        elif name == "b_fc":
            s[name] = (n, f)
        elif name == "w_fc":
            s[name] = (n, h, f)
        elif name == "w_proj":
            s[name] = (n, f, h)
        else:
            s[name] = (n, h, h)
    return s


@functools.partial(jax.jit, static_argnames=("sizes", "dtype"))
def _make(key, sizes, dtype):
    c = dict(sizes)
    shapes = _shapes(c)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    resid = 0.02 / (2.0 * c["n_layer"]) ** 0.5     # GPT-2's residual scaling
    out = {}
    for name, shape in shapes.items():
        if name.endswith("_g"):
            w = 1.0 + 0.02 * jax.random.normal(keys[name], shape, jnp.float32)
        elif len(shape) == 1 or name.startswith(("b", "ln")):
            w = 0.02 * jax.random.normal(keys[name], shape, jnp.float32)
        else:
            std = resid if name in ("wo", "w_proj") else 0.02
            w = std * jax.random.normal(keys[name], shape, jnp.float32)
        out[name] = w
    out = {k: v.astype(dtype) for k, v in
           _stress(out, c["n_head"], resid).items()}
    # near-twin tokens: every odd row of the embedding (which is also the
    # head) is its even neighbour plus a five-hundredth of its spread, so at every
    # position the two best logits are a near-tie whose winner the arithmetic's
    # precision decides.  Greedy tokens are all the check can see of the
    # program's logits; this makes them a sensitive reading of them.
    v = shapes["wte"][0] // 2
    wte = out["wte"].astype(jnp.float32)
    twins = wte[0:2 * v:2] + TWIN_SPREAD * 0.02 * jax.random.normal(
        keys["ln_f_g"], (v, shapes["wte"][1]), jnp.float32)
    out["wte"] = wte.at[1:2 * v:2].set(twins).astype(dtype)
    return out


def _stress(w: dict, n_head: int, resid: float) -> dict:
    """Outliers where exact arithmetic never looks (see the module's text).
    Dead inputs: LayerNorm channel 0 (gain and offset 0) feeds row 0 of wq,
    wk, wv and w_fc; MLP unit 0 (w_fc column and bias 0, and gelu(0) = 0)
    feeds row 0 of w_proj; in every head, value channel 2 (wv column and
    bias 0) feeds its row of wo.  Dead outputs: in every head, key channel 0
    holds a constant that query channel 0 (wq column and bias 0) multiplies
    by zero, and value channel 1 holds a constant that wo's zero row drops."""
    n_layer, h, _ = w["wq"].shape
    d = h // n_head
    k0, v1, v2 = (jnp.arange(n_head) * d + i for i in (0, 1, 2))
    sign = jnp.where(jnp.arange(4 * h) % 2 == 0, 1.0, -1.0)
    w["wo"] = w["wo"] * ATTN_GAIN
    for name in ("ln1_g", "ln1_b", "ln2_g", "ln2_b"):
        w[name] = w[name].at[:, 0].set(0.0)
    for name in ("wq", "wk", "wv", "w_fc"):
        w[name] = w[name].at[:, 0, :].set(
            WEIGHT_OUTLIER * 0.02 * sign[:w[name].shape[2]])
    w["w_fc"] = w["w_fc"].at[:, :, 0].set(0.0)
    w["b_fc"] = w["b_fc"].at[:, 0].set(0.0)
    w["w_proj"] = w["w_proj"].at[:, 0, :].set(WEIGHT_OUTLIER * resid * sign[:h])
    w["wv"] = w["wv"].at[:, :, v2].set(0.0)
    w["bv"] = w["bv"].at[:, v2].set(0.0)
    w["wo"] = w["wo"].at[:, v2, :].set(
        WEIGHT_OUTLIER * ATTN_GAIN * resid * sign[None, None, :h])
    spread = 0.02 * h ** 0.5            # of a key's or a value's channel
    w["wq"] = w["wq"].at[:, :, k0].set(0.0)
    w["bq"] = w["bq"].at[:, k0].set(0.0)
    w["bk"] = w["bk"].at[:, k0].set(KV_OUTLIER * spread)
    w["bv"] = w["bv"].at[:, v1].set(KV_OUTLIER * spread)
    w["wo"] = w["wo"].at[:, v1, :].set(0.0)
    return w


def make_weights(seed: int, sizes: dict, dtype) -> dict:
    """Seeded GPT-2 weights (N(0, 0.02) matrices, residual projections scaled
    by 1/sqrt(2L); biases and LayerNorm offsets get small seeded values too, so
    that no live term of the forward is multiplied by zero; odd vocabulary rows are
    near-twins of their even neighbours, see ``_make``; outliers in dead
    channels, see ``_stress``) in ``dtype``."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    picked = tuple(sorted((k, int(sizes[k])) for k in
                          ("n_embd", "n_head", "n_layer", "vocab_size",
                           "n_positions")))
    return _make(key, picked, jnp.dtype(dtype))


def _layer_norm(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head",))
def forward(w: dict, ids, n_head: int):
    """Logits (T, V) float32 for 0-based ``ids`` (T,), every position against
    its whole causal context."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        x = w["wte"][ids] + w["wpe"][:t]
        mask = jnp.tril(jnp.ones((t, t), bool))

        def block(x, lw):
            a = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
            q, k, v = (a @ lw["w" + n] + lw["b" + n] for n in "qkv")
            q, k, v = (y.reshape(t, n_head, -1).transpose(1, 0, 2)
                       for y in (q, k, v))
            s = q @ k.transpose(0, 2, 1) / jnp.sqrt(q.shape[-1] * 1.0)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            o = (p @ v).transpose(1, 0, 2).reshape(t, -1)
            x = x + o @ lw["wo"] + lw["bo"]
            m = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
            m = _gelu(m @ lw["w_fc"] + lw["b_fc"]) @ lw["w_proj"] + lw["b_proj"]
            return x + m, None

        x, _ = jax.lax.scan(block, x, {n: w[n] for n in _LAYER})
        x = _layer_norm(x, w["ln_f_g"], w["ln_f_b"])
        return x @ w["wte"].T


@jax.jit
def gaps_below_best(logits, positions, tokens):
    """For each (position, 0-based token): how far the token's reference logit
    lies below the reference's best at that position (0 when it IS the best)."""
    rows = logits[positions]
    return jnp.max(rows, -1) - jnp.take_along_axis(
        rows, tokens[:, None], axis=1)[:, 0]
