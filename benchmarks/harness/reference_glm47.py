"""Plain GLM-4.7-Flash (zai-org, ``model_type`` ``glm4_moe_lite``; the equations
are written out from its public ``config.json`` and, where that is silent, from
the family's published conventions: every such choice is under ``assumed`` in
the configuration file): seeded weights, the main model's full causal forward
and the PREDICTION MODULE's, in ``jax.numpy``, float32,
``default_matmul_precision("highest")``.  No kernels, no cache, no chunks, no
absorbed form, no drafting round, nothing imported from the program.

What the configuration file says is what runs: ``num_hidden_layers`` layers,
EVERY one's mixer latent attention with a compressed query; RMSNorm (eps
1e-5), pre-norm residual; the feed-forward half a dense SwiGLU of
``intermediate_size`` for l < ``first_k_dense_replace``, else routed; an untied
head.  With ``a = norm(x)``:

*MLA layer* (EXPANDED: keys and values up-projected, one full masked score
matrix a head, rows in blocks so that 8,192 positions fit)::

    q = rmsnorm(a W_qa) W_qb            (q_lora_rank 768; a head [q_n (192) ; q_r' (64)])
    [c' ; k_r'] = a W_dkv  (512 + 64)   c = rmsnorm(c')
    q_r, k_r = rotary(q_r', k_r')       theta 1e6, all 64 lanes, k_r shared by the heads
    [k_n,h ; v_h] = c W_ukv,h           (192 + 256 a head)
    s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(256),  j <= t
    out = concat_h(softmax(s_h) v_h) Wo                     (no gate)

*Routed half* (``noaux_tc``, ``n_group`` 1): ``s = sigmoid(x Wr)`` over all 64
in float32; ``s' = s + bias``; the 4 largest ``s'``; their weights the UNBIASED
``s`` renormalised times 1.8; plus one shared expert of 1,536, whole.  Every
expert is held here (``held_experts``: all 64).

*The prediction module* (``num_nextn_predict_layers`` 1; DeepSeek-V3's, section
2.2 of its report, with the GLM-4.5 family's names).  With ``h_t`` the main
model's last block's output at t BEFORE the final norm and ``x_{t+1}`` the
token that follows::

    z_t = [rmsnorm_e(Emb(x_{t+1})) ; rmsnorm_h(h_t)] W_eh   (4,096 -> 2,048)
    g = Block(z)      one block of the routed kind, causal over the pairs, pair t rotated at t
    draft logits for x_{t+2} = Head(rmsnorm_s(g_t))         the MAIN model's Emb and Head

It is ``make_layer``'s layer ``num_hidden_layers``, and the row its block caches
for pair t is :func:`mla_row` of ``z``.

The seeded weights are Ling's (``reference_ling3``: near-twin head columns, a
constant stream channel 0, half a stream a sublayer, router gains that are
the same for every seed and nearer even than Ling's (``EXPERT_SIGMA``), latent
scores sharp enough that a query reads a few tens of positions), WITH A DRAFTER WORTH RUNNING: at plain random weights a
prediction module agrees with the main model once in 154,880 tokens.  So the
head carries a seeded NEXT-TOKEN component: for a seeded permutation ``pi`` of
the ids, column ``pi(v)`` leans toward ``Emb[v]`` by ``BIGRAM_GAIN * u_v`` head
spreads (``u_v`` uniform in (0, 2), by token, so that the lean wins at a SHARE
of the tokens and not at all or none), and ``W_eh`` lets the embedding's half
through (``EH_EMBED``) beside a random mix of the hidden half (``EH_HIDDEN``).
The main model's best token after ``x_t`` is then ``pi(x_t)`` where ``u`` is
large, and the module, which sees ``x_{t+1}``, scores ``pi(x_{t+1})`` best at a
like share.  NOTHING IS SET: every product of both models is carried out, no
draft comes from an oracle, and the acceptance is what the two forwards give.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference_gpt2 import gaps_below_best  # noqa: F401  (the check's reading, shared)

TWIN_SPREADS = (1e-4, 1e-1)  # of a twin column about its neighbour, in spreads
HEAD_SPREAD = 0.02          # of a head column's entries
# the next-token lean of head column pi(v) toward Emb[v], at its mean over the
# tokens (u_v uniform in (0, 2)), in units of what the largest of the V other
# logits reads against a stream that is Emb[v] alone (sqrt(2 ln V / hidden) head
# spreads): the lean wins where gain * u_v * (the embedding's share of the
# normed stream) > 1, at any width and vocabulary.  TUNED ONCE ON THE CHIP so
# that the cell's acceptance lies in 0.60-0.85: the readings are in the
# configuration file (``bigram_readings``).
BIGRAM_GAIN = 6.0
EH_EMBED = 1.0              # W_eh's embedding half: this times the identity
EH_HIDDEN = 0.5             # ... its hidden half: a random mix of this spread
MLA_SCORE_SPREAD = 13.0     # variance of a latent layer's score (reference_ling3)
MLA_OUT_GAIN = 3.0          # on Wo (reference_ling3)
ROUTER_SPREAD = 2.0         # of a router logit, mean over experts
ROUTER_SHIFT = 8.0          # what the constant stream channel takes off every logit
# log-normal gain of a router column, by expert: gains of 0.70 to 1.43, so that
# a round's 512 picks land on 98% of a layer's 64 experts, as a router balanced
# by noaux_tc's bias reads at 128 tokens a round.  Ling's 0.5 (77-79% hit) made
# the cell's rate the seed's: an attention sublayer's output carries a component
# common to a sequence's positions, the router adds it to every token's logits
# as one offset an expert, and with four shared prefixes a run has four such
# offset vectors a layer, drawn by its seed: the hit share moved 1.6% by the
# seed, every hit is 18.9 MB of a round, and `out_tokens_per_s` spread by more
# than half its bound (PERF.md, Findings, PR 40)
EXPERT_SIGMA = 0.15
BIAS_SPREAD = 0.005         # of the router's selection bias
SUBLAYER = 0.5              # what a sublayer adds, relative to a unit stream
MLA_ROWS = 256              # query rows of a score matrix at a time


# -- the configuration, by layer ---------------------------------------------
def n_blocks(c: dict) -> int:
    """The main model's layers and the prediction module's block behind them."""
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def is_dense(c: dict, layer: int) -> bool:
    return layer < c["first_k_dense_replace"]


# -- seeded weights ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


_NAMES = ("embed", "head", "twins", "twin_spreads", "norm_f", "ln1", "ln2",
          "wq_a", "q_norm", "wq_b", "wk", "wv", "wo", "router", "e_gate",
          "e_up", "e_down", "s_gate", "s_up", "s_down", "w_dkv", "kv_norm",
          "w_ukv", "d_gate", "d_up", "d_down", "enorm", "hnorm", "eh_proj",
          "mtp_norm", "pi", "lean")


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


def _ffn_weights(seed: int, c: dict, layer: int, dt, mat) -> dict:
    h = c["hidden_size"]
    if is_dense(c, layer):
        f = c["intermediate_size"]
        sg, su, sd = _swiglu_stds(h, f)
        return {"d_gate": mat("d_gate", (h, f), sg), "d_up": mat("d_up", (h, f), su),
                "d_down": mat("d_down", (f, h), sd).at[:, 0].set(0.0)}
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    g = c["moe_intermediate_size"] * c["n_shared_experts"]
    sg, su, sd = _swiglu_stds(h, f)
    # the routed sum's weights add up to routed_scaling_factor, and every
    # expert is held: the routed half adds SUBLAYER, the shared expert as much
    sd_routed = sd / c["routed_scaling_factor"]
    gain = jnp.asarray(np.exp(EXPERT_SIGMA * _quantiles(e, layer, 1000)))
    router = (jax.random.normal(_key(seed, layer, "router"), (h, e), jnp.float32)
              * (ROUTER_SPREAD / math.sqrt(h)) * gain / jnp.mean(gain))
    # the stream's constant channel 0 as the router's offset: scores lie well
    # under 1/2 (reference_ling3)
    router = router.at[0, :].set(-ROUTER_SHIFT)
    w = {"router": router.astype(dt),
         "router_bias": jnp.asarray(BIAS_SPREAD * _quantiles(e, layer, 2000),
                                    jnp.float32),
         "e_gate": mat("e_gate", (e, h, f), sg), "e_up": mat("e_up", (e, h, f), su),
         "e_down": mat("e_down", (e, f, h), sd_routed).at[:, :, 0].set(0.0)}
    sg, su, sd = _swiglu_stds(h, g)
    w.update(s_gate=mat("s_gate", (h, g), sg), s_up=mat("s_up", (h, g), su),
             s_down=mat("s_down", (g, h), sd).at[:, 0].set(0.0))
    return w


def make_layer(seed: int, c: dict, layer: int, dtype) -> dict:
    """One block's weights in ``dtype``, on the device, tensor by tensor; block
    ``num_hidden_layers`` is the prediction module's, with its two input norms,
    ``eh_proj`` and its final norm beside it."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    dt = jnp.dtype(dtype)
    unit = 1.0 / math.sqrt(h)

    def mat(name, shape, std):
        return _normal(_key(seed, layer, name), tuple(shape), std, dt)

    dn, dr, dv, rank, qr = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                            c["v_head_dim"], c["kv_lora_rank"], c["q_lora_rank"])
    # a score's spread is the product of a query lane's and a key lane's
    qk = MLA_SCORE_SPREAD ** 0.25
    w_dkv = jnp.concatenate([mat("w_dkv", (h, rank), unit),
                             mat("wk", (h, dr), qk * unit)], axis=1)
    w_ukv = jnp.concatenate(
        [mat("w_ukv", (rank, heads, dn), qk / math.sqrt(rank)),
         mat("wv", (rank, heads, dv), 1.0 / math.sqrt(rank))], axis=2)
    w = {"ln1": 1.0 + mat("ln1", (h,), 0.02), "ln2": 1.0 + mat("ln2", (h,), 0.02),
         # the normed down-projection has unit lanes: the up-projection alone
         # sets a query lane's spread
         "wq_a": mat("wq_a", (h, qr), unit),
         "q_norm": 1.0 + mat("q_norm", (qr,), 0.02),
         "wq_b": mat("wq_b", (qr, heads, dn + dr), qk / math.sqrt(qr)),
         "w_dkv": w_dkv, "kv_norm": 1.0 + mat("kv_norm", (rank,), 0.02),
         "w_ukv": w_ukv,
         # (``mla_out_gain``: a toy's contexts are tens of positions, where a
         # softmax averages little and the published gain would drown the stream)
         "wo": mat("wo", (heads, dv, h),
                   float(c.get("mla_out_gain", MLA_OUT_GAIN)) * 2 * SUBLAYER
                   / math.sqrt(heads * dv)).at[:, :, 0].set(0.0)}
    w.update(_ffn_weights(seed, c, layer, dt, mat))
    if layer >= c["num_hidden_layers"]:
        # W_eh: the embedding's half let through, the hidden half mixed in
        eh = jnp.concatenate(
            [EH_EMBED * jnp.eye(h, dtype=jnp.float32),
             _normal(_key(seed, layer, "eh_proj"), (h, h), EH_HIDDEN * unit,
                     jnp.float32)], axis=0)
        w.update(enorm=1.0 + mat("enorm", (h,), 0.02),
                 hnorm=1.0 + mat("hnorm", (h,), 0.02),
                 eh_proj=eh.astype(dt),
                 mtp_norm=1.0 + mat("mtp_norm", (h,), 0.02))
    return w


def next_token_map(seed: int, c: dict):
    """(``pi`` (V,) int32, a seeded permutation of the ids; ``u`` (V,) float32
    uniform in (0, 2): how hard head column ``pi(v)`` leans toward ``Emb[v]``,
    by token)."""
    v = c["vocab_size"]
    return (jax.random.permutation(_key(seed, -1, "pi"), v).astype(jnp.int32),
            jax.random.uniform(_key(seed, -1, "lean"), (v,), jnp.float32, 0.0, 2.0))


def make_ends(seed: int, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head over the whole vocabulary:
    near-twin columns, then the next-token lean (module text; the
    configuration's ``bigram_gain`` where it states one: a toy's contexts are
    short, and its latent sublayers louder)."""
    h, v, dt = c["hidden_size"], c["vocab_size"], jnp.dtype(dtype)
    gain = float(c.get("bigram_gain", BIGRAM_GAIN))
    embed = _normal(_key(seed, -1, "embed"), (v, h), 1.0, dt).at[:, 0].set(1.0)
    head = _normal(_key(seed, -1, "head"), (h, v), HEAD_SPREAD, jnp.float32)
    lo, hi = (math.log(s) for s in TWIN_SPREADS)
    spreads = jnp.exp(jax.random.uniform(_key(seed, -1, "twin_spreads"),
                                         (v // 2,), minval=lo, maxval=hi))
    twins = head[:, 0:2 * (v // 2):2] + spreads * _normal(
        _key(seed, -1, "twins"), (h, v // 2), HEAD_SPREAD, jnp.float32)
    head = head.at[:, 1:2 * (v // 2):2].set(twins)
    pi, u = next_token_map(seed, c)
    # column pi(v) += gain * u_v spreads of Emb[v] (its constant channel 0,
    # which every token shares, left out)
    unit = HEAD_SPREAD * math.sqrt(2.0 * math.log(v) / h)
    lean = (gain * unit) * u[:, None] * embed.astype(jnp.float32).at[:, 0].set(0.0)
    head = (head + lean[jnp.argsort(pi)].T).astype(dt)     # column pi(v) += lean[v]
    return {"embed": embed, "head": head,
            "norm_f": (1.0 + _normal(_key(seed, -1, "norm_f"), (h,), 0.02, dt)
                       ).astype(dt)}


def make_weights(seed: int, c: dict, dtype) -> dict:
    """All of it: ``{"embed", "head", "norm_f", "layers": [...]}``, the
    prediction module's block last of ``layers``."""
    return dict(make_ends(seed, c, dtype),
                layers=[make_layer(seed, c, l, dtype) for l in range(n_blocks(c))])


# -- the forward -----------------------------------------------------------------
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


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
    """(T, n_routed_experts) weights, the router's steps: sigmoid scores over
    ALL experts in float32; the selection bias added; the top-k of the biased
    scores chosen (``n_group`` 1: no groups); weighed by the score alone,
    renormalised and scaled; zero elsewhere."""
    t = m.shape[0]
    s = jax.nn.sigmoid(m @ router)
    _, idx = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(top)


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
    EXPANDED: the query compressed and normed, every position's keys and values
    up-projected from its latent, the masked score matrix ``rows`` query rows at
    a time."""
    t = a.shape[0]
    dn, dr, rank = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    q = jnp.einsum("tr,rnd->tnd",
                   _rms(a @ w["wq_a"], w["q_norm"], c["rms_norm_eps"]), w["wq_b"])
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
    return jnp.einsum("tnd,ndh->th", o, w["wo"])


def routed_half(c: dict, w: dict, m):
    """m (T, hidden), the normed input -> (the routed sum over all the
    experts, the shared expert's output)."""
    weights = routing(c, m, w["router"], w["router_bias"])

    def expert(y, ew):      # every token through expert e, weighted (0: not chosen)
        return y + ew[3][:, None] * _swiglu(m, ew[0], ew[1], ew[2]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (w["e_gate"], w["e_up"], w["e_down"], weights.T))
    return y, _swiglu(m, w["s_gate"], w["s_up"], w["s_down"])


def layer_forward(c: dict, layer: int, w: dict, x):
    """One block on one sequence: x (T, hidden) float32 -> the same (the
    prediction module's block, ``layer = num_hidden_layers``, is of the routed
    kind)."""
    eps = c["rms_norm_eps"]
    x = x + mla_mixer(c, w, _rms(x, w["ln1"], eps))
    m = _rms(x, w["ln2"], eps)
    if is_dense(c, layer):
        return x + _swiglu(m, w["d_gate"], w["d_up"], w["d_down"])
    routed, shared = routed_half(c, w, m)
    return x + routed + shared


def mtp_input(c: dict, w: dict, embed, hidden, ids):
    """The pairs' inputs: ``hidden`` (T, hidden) the main model's last block's
    output BEFORE the final norm, ``ids`` (T,) the sequence -> z (T - 1,
    hidden), pair t of (hidden[t], ids[t + 1]), the embedding's half first."""
    eps = c["rms_norm_eps"]
    return jnp.concatenate([_rms(embed[ids[1:]], w["enorm"], eps),
                            _rms(hidden[:-1], w["hnorm"], eps)], -1) @ w["eh_proj"]


def forward(w: dict, c: dict, ids, both: bool = False):
    """Logits (T, V) for one sequence from weights held whole (toy sizes);
    ``both``: -> (logits, the hidden states before the final norm (T, hidden),
    the prediction module's logits (T - 1, V): row t scores the token at t +
    2, the row the module's block caches a pair (T - 1, lanes))."""
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids)
        embed, head = f32(w["embed"]), f32(w["head"])
        x = embed[ids]
        for layer in range(c["num_hidden_layers"]):
            x = layer_forward(c, layer, jax.tree_util.tree_map(
                f32, w["layers"][layer]), x)
        logits = _rms(x, f32(w["norm_f"]), eps) @ head
        if not both:
            return logits
        layer = c["num_hidden_layers"]
        wm = jax.tree_util.tree_map(f32, w["layers"][layer])
        z = mtp_input(c, wm, embed, x, ids)
        row = mla_row(c, wm, _rms(z, wm["ln1"], eps))
        g = layer_forward(c, layer, wm, z)
        return logits, x, _rms(g, wm["mtp_norm"], eps) @ head, row


# -- the check's replay: a request at a time, a layer's weights at a time ---------
@jax.jit
def _reduce_block(head, norm, eps, xb, ib):
    with jax.default_matmul_precision("highest"):
        lg = _rms(xb, norm, eps) @ head
    return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32),
            jnp.take_along_axis(lg, ib[:, None], axis=-1)[:, 0])


def _reduce_rows(ends, eps, x, norm, ids, block: int = 256):
    """Logit rows of ``x`` (n, hidden) under ``norm`` and the head, reduced a
    block at a time (a row is the whole vocabulary wide): -> (the best logit
    (n,), its id (n,), the logit of ``ids`` (n,)).  (The head is an ARGUMENT of
    the jitted block: closed over, its 1.27 GB are a constant of every program
    that is compiled, and ten requests' reduction took 40 minutes on the chip:
    my chip run, PR 40, call 2.)"""
    n = x.shape[0]
    if not n:
        return np.zeros((0,), np.float32), np.zeros((0,), np.int32), np.zeros(
            (0,), np.float32)
    pad = -n % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    ib = jnp.pad(jnp.asarray(ids, jnp.int32), (0, pad)).reshape(-1, block)
    out = [_reduce_block(ends["head"], norm, eps, a, b) for a, b in zip(xb, ib)]
    return tuple(np.concatenate([np.asarray(o[i]) for o in out])[:n]
                 for i in range(3))


def replay_requests(seed: int, c: dict, dtype, requests: list, served: list,
                    drafted: list, latent_at=None):
    """Each 0-based id sequence of ``requests`` (padded alike; causal, so the
    padding is never seen) through both models, layer by layer, one block's
    float32 weights on the device at a time.  ``served[i]`` = (rows, ids): the
    main model's logit rows to reduce and the ids whose logits are wanted there
    (the served tokens); ``drafted[i]`` = (pairs, ids): the same for the
    prediction module's rows, by PAIR index (pair t scores the token at t + 2).
    ``latent_at[i]``: positions whose cached rows are wanted.  -> a list of
    dicts: ``main`` and ``mtp`` = (best, best id, logit of the given ids),
    ``rows`` (blocks, positions, lanes): :func:`mla_row` of every main layer at
    ``latent_at`` and, last, of the module's block at the pairs ``latent_at -
    1`` (the row a serving cache stores one position on)."""
    f32 = lambda w: jax.tree_util.tree_map(         # noqa: E731
        lambda a: a.astype(jnp.float32), w)
    eps = c["rms_norm_eps"]
    ends = f32(make_ends(seed, c, dtype))
    n_main = c["num_hidden_layers"]
    ids = [jnp.asarray(r) for r in requests]
    xs = [ends["embed"][i] for i in ids]
    rows = [[] for _ in requests]

    def run_layer(layer, w, x):
        with jax.default_matmul_precision("highest"):
            return layer_forward(c, layer, w, x)

    @jax.jit
    def rows_at(w, x, at):
        with jax.default_matmul_precision("highest"):
            return mla_row(c, w, _rms(x, w["ln1"], eps))[at]

    @jax.jit
    def pairs_of(w, embed, x, i):
        with jax.default_matmul_precision("highest"):
            return mtp_input(c, w, embed, x, i)

    out = [dict() for _ in requests]
    for layer in range(n_blocks(c)):
        w = f32(make_layer(seed, c, layer, dtype))
        if layer == n_main:
            # the main model is done: its rows, then the module's inputs
            for i, x in enumerate(xs):
                out[i]["main"] = _reduce_rows(ends, eps, x[jnp.asarray(served[i][0])],
                                              ends["norm_f"], served[i][1])
            xs = [pairs_of(w, ends["embed"], x, i) for x, i in zip(xs, ids)]
        if latent_at is not None:
            for i, x in enumerate(xs):
                at = jnp.asarray(latent_at[i]) - (layer == n_main)
                rows[i].append(rows_at(w, x, jnp.maximum(at, 0)))
        fn = jax.jit(functools.partial(run_layer, layer))
        xs = [fn(w, x).block_until_ready() for x in xs]
        del fn
        if layer == n_main:
            for i, x in enumerate(xs):
                out[i]["mtp"] = _reduce_rows(ends, eps, x[jnp.asarray(drafted[i][0])],
                                             w["mtp_norm"], drafted[i][1])
        del w
    for i in range(len(requests)):
        out[i]["rows"] = None if latent_at is None else np.stack(
            [np.asarray(r) for r in rows[i]])
    return out
