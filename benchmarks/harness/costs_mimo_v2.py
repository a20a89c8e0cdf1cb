"""Operations and bytes MiMo-V2-Flash's decode round and its prefills need,
from the configuration file's shapes alone, at the UNPADDED widths whatever
the arenas' lay-out.  Hand-worked lines are in PERF.md section 3;
``benchmarks/tests/test_costs_mimo_v2.py`` holds the functions to those
numbers."""


def layer_kinds(c: dict) -> list:
    """[(sliding?, routed?)] for the layers that are run."""
    return [(bool(c["hybrid_layer_pattern"][l]), bool(c["moe_layer_freq"][l]))
            for l in range(c["num_hidden_layers"])]


def kv_heads(c: dict, sliding: bool) -> int:
    return c["swa_num_key_value_heads" if sliding else "num_key_value_heads"]


def attention_matmul_params(c: dict, sliding: bool) -> int:
    """Wq, Wk, Wv, Wo of a layer of its kind."""
    h, heads, d, dv = (c["hidden_size"], c["num_attention_heads"],
                       c["head_dim"], c["v_head_dim"])
    n_kv = kv_heads(c, sliding)
    return h * heads * d + h * n_kv * d + h * n_kv * dv + heads * dv * h


def attention_params(c: dict, sliding: bool) -> int:
    """... and the sinks, one a query head, where the kind has them."""
    sink = c["add_swa_attention_sink_bias" if sliding
             else "add_full_attention_sink_bias"]
    return attention_matmul_params(c, sliding) + (
        c["num_attention_heads"] if sink else 0)


def ffn_fixed_params(c: dict, routed: bool) -> int:
    """What a layer's feed-forward half reads whatever the batch: a dense
    layer's SwiGLU, or a routed layer's router and selection bias (no shared
    expert)."""
    h = c["hidden_size"]
    if not routed:
        return 3 * h * c["intermediate_size"]
    return h * c["experts_published"] + c["experts_published"]


def expert_bytes(c: dict, dtype_bytes: int) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def head_bytes(c: dict, dtype_bytes: int) -> int:
    return c["hidden_size"] * c["vocab_size"] * dtype_bytes


def decode_fixed_bytes(c: dict, dtype_bytes: int) -> int:
    """Bytes of weights one decode round reads whatever the batch: every
    layer's attention and fixed feed-forward part, the norm vectors, the head.
    Not the embedding (a row a token)."""
    h = c["hidden_size"]
    total = (2 * c["num_hidden_layers"] + 1) * h + h * c["vocab_size"]
    for sliding, routed in layer_kinds(c):
        total += attention_params(c, sliding) + ffn_fixed_params(c, routed)
    return total * dtype_bytes


def kv_bytes_per_position_layer(c: dict, sliding: bool, dtype_bytes: int) -> int:
    """What one cached position holds in one layer of its kind: keys of 192
    and values of 128 lanes a K/V head, unpadded."""
    return kv_heads(c, sliding) * (c["head_dim"] + c["v_head_dim"]) * dtype_bytes


def decode_parts_bytes(c: dict, dtype_bytes: int, rounds: float,
                       experts_hit: int, ctx_tokens: int,
                       window_tokens: int) -> dict:
    """The least a set of decode rounds has to move, by part: the fixed
    weights once a round; an expert's matrices for every (layer, round,
    expert) hit, by the program's own count; every full layer's K/V of every
    position a slot may see (``ctx_tokens``, summed over slots and rounds);
    every sliding layer's of the positions inside the window
    (``window_tokens``)."""
    kinds = layer_kinds(c)
    full = sum(not s for s, _ in kinds)
    sliding = sum(s for s, _ in kinds)
    return {"fixed": rounds * decode_fixed_bytes(c, dtype_bytes),
            "experts": experts_hit * expert_bytes(c, dtype_bytes),
            "full_kv": full * ctx_tokens * kv_bytes_per_position_layer(
                c, False, dtype_bytes),
            "window_kv": sliding * window_tokens * kv_bytes_per_position_layer(
                c, True, dtype_bytes)}


def decode_least_bytes(c: dict, dtype_bytes: int, rounds: float,
                       experts_hit: int, ctx_tokens: int,
                       window_tokens: int) -> float:
    return sum(decode_parts_bytes(c, dtype_bytes, rounds, experts_hit,
                                  ctx_tokens, window_tokens).values())


def windowed_pairs(t0: int, ts: int, window: int) -> int:
    """(query, key) pairs a sliding layer scores for ``ts`` queries at
    positions ``t0 ..``: a query at p sees ``min(p + 1, window)`` keys."""
    return sum(min(p + 1, window) for p in range(t0, t0 + ts))


def prefill_flops(c: dict, chunks: list, assignments: int) -> float:
    """Multiply-adds x 2 a set of prefill programs needs, by TRUE lengths:
    ``chunks`` is ``[(prefix_len, tokens)]``.  For every token the matrix
    products of every layer outside the routed experts; for every (query,
    key) pair every query head's score over 192 lanes and weighted sum over
    128 -- a full layer's pairs causal over prefix and own tokens, a SLIDING
    layer's inside the window only; an expert's three matrices for every
    assignment that landed on a held expert (``assignments``: the program's
    count); the head for ONE position a program."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    kinds = layer_kinds(c)
    per_token = sum(2 * (attention_matmul_params(c, s)
                         + (h * c["experts_published"] if r
                            else 3 * h * c["intermediate_size"]))
                    for s, r in kinds)
    per_pair = 2 * heads * (c["head_dim"] + c["v_head_dim"])
    full = sum(not s for s, _ in kinds)
    sliding = sum(s for s, _ in kinds)
    total = 0.0
    for p, ts in chunks:
        causal = ts * p + ts * (ts + 1) // 2
        total += (ts * per_token
                  + per_pair * (full * causal + sliding * windowed_pairs(
                      p, ts, c["sliding_window"]))
                  + 2 * h * c["vocab_size"])
    return total + assignments * 6 * h * c["moe_intermediate_size"]


#: LMServingEngine's decode step and prefills in a device trace
ROUND_MODULES = ("decode_fn",)
PREFILL_MODULES = ("prefill_fn",)


def traced_rounds(rec: dict):
    """:func:`decode_parts_bytes` of a recording's traced rounds, from the
    program's own counters over them (the args of its ``lm/decode_step``
    spans); None where they hold nothing to read (a program without the
    args, a run without rounds)."""
    import jax.numpy as jnp
    counters = rec["counters"]
    rounds = counters.get("lm.traced_decode_rounds")
    hit = counters.get("lm.traced_moe_experts_hit")
    ctx = counters.get("lm.traced_ctx_tokens")
    window = counters.get("lm.traced_window_tokens")
    if not rounds or not hit or not ctx or not window:
        return None
    c = rec["config"]
    dtype_bytes = jnp.dtype(c["assumed"]["serve_dtype"]).itemsize
    return decode_parts_bytes(c, dtype_bytes, rounds, hit, ctx, window)


def modules_device_s(rec: dict, names) -> float:
    return sum(m["device_s"] for name, m in rec["trace"]["modules"].items()
               if any(n in name for n in names))
