"""Operations and bytes Laguna-S-2.1's programs need, from the configuration
file's shapes alone (the chip's share: the experts and vocabulary rows held
here).  Hand-worked lines are in PERF.md section 3; ``tests/test_costs_laguna.py``
holds the functions to those numbers."""


def _attention_params(c: dict, heads: int) -> int:
    h, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    return h * heads * d * 2 + 2 * h * kv * d + h * heads     # q, o; k, v; gate


def _layers(c: dict):
    """(query heads, sliding?, sparse?) of the layers that are run."""
    return [(c["num_attention_heads_per_layer"][l],
             c["layer_types"][l] == "sliding_attention",
             c["mlp_layer_types"][l] == "sparse")
            for l in range(c["num_hidden_layers"])]


def expert_bytes(c: dict, dtype_bytes: int) -> int:
    """One routed expert's three matrices: what a round reads for every
    distinct expert one of its tokens is routed to."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def decode_fixed_bytes(c: dict, dtype_bytes: int) -> int:
    """Bytes of weights one decode round reads whatever the batch: attention,
    norms, the dense MLP, routers, shared experts, the final norm and the held
    head.  Not the routed experts (by the round's own count of experts hit)
    and not the embedding (one row a slot)."""
    h = c["hidden_size"]
    total = h + h * c["vocab_size"]
    for heads, _, sparse in _layers(c):
        total += _attention_params(c, heads) + 2 * h
        total += (h * c["experts_published"]
                  + 3 * h * c["shared_expert_intermediate_size"]
                  if sparse else 3 * h * c["intermediate_size"])
    return total * dtype_bytes


def kv_bytes_per_position_layer(c: dict, dtype_bytes: int) -> int:
    """Keys and values of one cached position in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def decode_least_bytes(c: dict, dtype_bytes: int, rounds: float,
                       experts_hit: int, context_tokens: int,
                       window_tokens: int) -> float:
    """The least a set of decode rounds has to read: the fixed weights once a
    round, an expert's matrices for every (layer, round, expert) hit, and for
    every decoded token the K/V of the positions it may see: its whole context
    on a full layer (``context_tokens`` summed over the tokens), at most the
    window on a sliding one (``window_tokens``)."""
    full = sum(not sliding for _, sliding, _ in _layers(c))
    sliding = sum(s for _, s, _ in _layers(c))
    return (rounds * decode_fixed_bytes(c, dtype_bytes)
            + experts_hit * expert_bytes(c, dtype_bytes)
            + kv_bytes_per_position_layer(c, dtype_bytes)
            * (full * context_tokens + sliding * window_tokens))


def prefill_flops(c: dict, t: int, moe_assignments: int) -> int:
    """Multiply-adds x 2 a prefill of ``t`` tokens needs: projections, the
    causal (and windowed) half of the attention scores and values, the MLPs,
    the routers and shared experts, the held experts' matmuls for the
    assignments that landed here, the head for one position."""
    h, d, w = c["hidden_size"], c["head_dim"], c["sliding_window"]
    causal = t * (t + 1) // 2
    windowed = causal if t <= w else w * (w + 1) // 2 + (t - w) * w
    total = 2 * h * c["vocab_size"]
    for heads, sliding, sparse in _layers(c):
        total += 2 * t * _attention_params(c, heads)
        total += 4 * heads * d * (windowed if sliding else causal)
        total += (2 * t * h * c["experts_published"]
                  + 6 * t * h * c["shared_expert_intermediate_size"]
                  if sparse else 6 * t * h * c["intermediate_size"])
    return total + 6 * h * c["moe_intermediate_size"] * moe_assignments
