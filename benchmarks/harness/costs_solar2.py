"""Operations and bytes Solar-Open2-250B's programs need, from the
configuration file's shapes alone (the chip's share: the experts and
vocabulary rows held here).  Hand-worked lines are in PERF.md section 3;
``tests/test_costs_solar2.py`` holds the functions to those numbers."""


def _kda(c: dict):
    la = c["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def _layers(c: dict) -> list:
    """True for a softmax layer, False for a KDA layer, of the layers run."""
    return [l in c["gqa_layers"] for l in range(c["num_hidden_layers"])]


def softmax_params(c: dict) -> int:
    """q, o and the elementwise gate over the query heads; k, v over the K/V
    heads."""
    h, d = c["hidden_size"], c["head_dim"]
    return 3 * h * c["num_attention_heads"] * d + 2 * h * c["num_key_value_heads"] * d


def kda_matmul_params(c: dict) -> int:
    """q, k, v, o; the decay's and the gate's low-rank pairs (rank = the
    head's size); beta a head."""
    h = c["hidden_size"]
    heads, d, _ = _kda(c)
    return 4 * h * heads * d + 2 * (h * d + d * heads * d) + h * heads


def kda_params(c: dict) -> int:
    """... and the convolution's taps over the q, k and v channels, A_log a
    head, dt_bias a channel, the head norm's weight."""
    heads, d, taps = _kda(c)
    return kda_matmul_params(c) + taps * 3 * heads * d + heads + heads * d + d


def ffn_fixed_params(c: dict) -> int:
    """What every layer's routed half reads whatever the batch: the router,
    the shared expert, and the layer's two norm vectors."""
    h = c["hidden_size"]
    return (h * c["experts_published"]
            + 3 * h * c["moe_intermediate_size"] * c["n_shared_experts"] + 2 * h)


def expert_bytes(c: dict, dtype_bytes: int) -> int:
    """One routed expert's three matrices: what a round reads for every
    distinct expert one of its tokens is routed to."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def decode_fixed_bytes(c: dict, dtype_bytes: int) -> int:
    """Bytes of weights one decode round reads whatever the batch: the mixers,
    norms, routers, shared experts, the final norm and the held head.  Not the
    routed experts (by the round's own count of experts hit), not the
    embedding (one row a slot), not the router's selection bias (1,280 B)."""
    h = c["hidden_size"]
    total = h + h * c["vocab_size"]
    for softmax in _layers(c):
        total += (softmax_params(c) if softmax else kda_params(c)) + ffn_fixed_params(c)
    return total * dtype_bytes


def kv_bytes_per_position_layer(c: dict, dtype_bytes: int) -> int:
    """Keys and values of one cached position in one softmax layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def state_row_bytes(c: dict, dtype_bytes: int) -> int:
    """What a decode round reads AND writes for one active slot in one KDA
    layer: the float32 state a head, and the convolution's tail (the last
    taps - 1 inputs of the q, k and v channels, in the serving dtype)."""
    heads, d, taps = _kda(c)
    return 2 * (heads * d * d * 4 + (taps - 1) * 3 * heads * d * dtype_bytes)


def decode_least_bytes(c: dict, dtype_bytes: int, rounds: float,
                       experts_hit: int, context_tokens: int,
                       state_rows: int) -> float:
    """The least a set of decode rounds has to move: the fixed weights once a
    round, an expert's matrices for every (layer, round, expert) hit, for
    every decoded token the K/V of its whole context on each softmax layer
    (``context_tokens`` summed over the tokens), and for every (ACTIVE slot,
    KDA layer) of a round (``state_rows``) its state and tail, read and
    written.  An idle slot counts for nothing."""
    softmax = sum(_layers(c))
    return (rounds * decode_fixed_bytes(c, dtype_bytes)
            + experts_hit * expert_bytes(c, dtype_bytes)
            + kv_bytes_per_position_layer(c, dtype_bytes) * softmax * context_tokens
            + state_rows * state_row_bytes(c, dtype_bytes))


def prefill_flops(c: dict, t: int, moe_assignments: int) -> int:
    """Multiply-adds x 2 a prefill of ``t`` TRUE positions needs (a padded
    position counts for nothing): projections, the causal half of the softmax
    layers' scores and values, for a KDA layer its convolution and the
    recurrence's own count, 7 d_k d_v a head a position (the decay, S^T k,
    the rank-one update, S^T q) whatever form computes it, the routers and
    shared experts, the held experts' matmuls for the assignments that landed
    here, the head for one position."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, dk, taps = _kda(c)
    causal = t * (t + 1) // 2
    total = 2 * h * c["vocab_size"]
    for softmax in _layers(c):
        if softmax:
            total += 2 * t * softmax_params(c)
            total += 4 * c["num_attention_heads"] * d * causal
        else:
            total += 2 * t * kda_matmul_params(c)
            total += t * (2 * taps * 3 * heads * dk + 7 * heads * dk * dk)
        total += 2 * t * (ffn_fixed_params(c) - 2 * h)
    return total + 6 * h * c["moe_intermediate_size"] * moe_assignments


def traced_decode_least_bytes(rec: dict, rounds: float):
    """:func:`decode_least_bytes` of a recording's traced rounds, from the
    program's own counters over them; None where they hold nothing to read
    (a program without the state arena, a run without decode rounds)."""
    import jax.numpy as jnp
    counters = rec["counters"]
    hit = counters.get("lm.traced_moe_experts_hit")
    rows = counters.get("lm.traced_state_rows")
    if not rounds or not hit or not rows or not counters.get("lm.decode_context_tokens"):
        return None
    dtype_bytes = jnp.dtype(rec["config"]["assumed"]["serve_dtype"]).itemsize
    return decode_least_bytes(rec["config"], dtype_bytes, rounds, hit,
                              counters["lm.decode_context_tokens"], rows)
