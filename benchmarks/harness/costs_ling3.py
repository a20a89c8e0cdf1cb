"""Operations and bytes Ling-3.0-flash-VL's decode step needs, from the
configuration file's shapes alone (the chip's share: the experts and
vocabulary rows held here).  Hand-worked lines are in PERF.md section 3;
``tests/test_costs_ling3.py`` holds the functions to those numbers."""


def _layers(c: dict) -> list:
    """(is MLA, is dense) of the layers run."""
    return [((l + 1) % c["layer_group_size"] == 0, l < c["first_k_dense_replace"])
            for l in range(c["num_hidden_layers"])]


def kda_matmul_params(c: dict) -> int:
    """q, k, v, o; the decay's and the gate's full-rank projections; beta a
    head."""
    h, inner = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    return 6 * h * inner + h * c["num_attention_heads"]


def kda_params(c: dict) -> int:
    """... and the convolution's taps over the q, k and v channels, A_log a
    head, dt_bias a channel, the head norm's weight."""
    heads, d = c["num_attention_heads"], c["head_dim"]
    return (kda_matmul_params(c) + c["short_conv_kernel_size"] * 3 * heads * d
            + heads + heads * d + d)


def mla_matmul_params(c: dict) -> int:
    """Wq, W_dkv, W_ukv, the gate a head, Wo."""
    h, heads, rank = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return (h * heads * (dn + dr) + h * (rank + dr) + rank * heads * (dn + dv)
            + h * heads + heads * dv * h)


def mla_params(c: dict) -> int:
    """... and the latent's norm."""
    return mla_matmul_params(c) + c["kv_lora_rank"]


def ffn_fixed_params(c: dict, dense: bool) -> int:
    """What a layer's feed-forward half reads whatever the batch, without its
    two norm vectors: a dense layer's SwiGLU, or a routed layer's router and
    shared expert."""
    h = c["hidden_size"]
    if dense:
        return 3 * h * c["intermediate_size"]
    return (h * c["experts_published"]
            + 3 * h * c["moe_shared_expert_intermediate_size"])


def expert_bytes(c: dict, dtype_bytes: int) -> int:
    """One routed expert's three matrices: what a round reads for every
    distinct expert one of its tokens is routed to."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def decode_fixed_bytes(c: dict, dtype_bytes: int) -> int:
    """Bytes of weights one decode round reads whatever the batch: the mixers,
    norms, dense layers, routers, shared experts, the final norm and the held
    head.  Not the routed experts (by the round's own count of experts hit),
    not the embedding (one row a slot), not the router's selection bias
    (2,048 B a layer)."""
    h = c["hidden_size"]
    total = h + h * c["vocab_size"]
    for mla, dense in _layers(c):
        total += ((mla_params(c) if mla else kda_params(c))
                  + ffn_fixed_params(c, dense) + 2 * h)
    return total * dtype_bytes


def latent_bytes_per_position_layer(c: dict, dtype_bytes: int) -> int:
    """The one row a cached position holds in one MLA layer: the latent and
    the rotated lanes, as the model states them (576 lanes; the arena pads a
    row to 640, which is no part of the least)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * dtype_bytes


def state_row_bytes(c: dict, dtype_bytes: int) -> int:
    """What a decode round reads AND writes for one active slot in one KDA
    layer: the float32 state a head, and the convolution's tail (the last
    taps - 1 inputs of the q, k and v channels, in the serving dtype)."""
    heads, d = c["num_attention_heads"], c["head_dim"]
    taps = c["short_conv_kernel_size"]
    return 2 * (heads * d * d * 4 + (taps - 1) * 3 * heads * d * dtype_bytes)


def decode_parts_bytes(c: dict, dtype_bytes: int, rounds: float,
                       experts_hit: int, latent_positions: int,
                       state_rows: int) -> dict:
    """The least a set of decode rounds has to move, by part: the fixed
    weights once a round, an expert's matrices for every (layer, round,
    expert) hit, ONE row for every live position of every active slot on each
    MLA layer (``latent_positions``: the program's count, positions not whole
    blocks), and for every (ACTIVE slot, KDA layer) of a round
    (``state_rows``) its state and tail, read and written."""
    return {"fixed": rounds * decode_fixed_bytes(c, dtype_bytes),
            "experts": experts_hit * expert_bytes(c, dtype_bytes),
            "latent": latent_positions * latent_bytes_per_position_layer(
                c, dtype_bytes),
            "state": state_rows * state_row_bytes(c, dtype_bytes)}


def decode_least_flops(c: dict, tokens: int, assignments: int,
                       latent_positions: int) -> float:
    """Multiply-adds x 2 a set of decode rounds needs (nothing is per round:
    a round's work is its tokens'): for every decoded
    token (``tokens``: active slots summed over the rounds) the matrix
    products of every layer outside the routed experts and the head, a KDA
    layer's convolution and the recurrence's own count (7 d_k d_v a head: the
    decay, S^T k, the rank-one update, S^T q), the absorbed query's fold and
    the values' unfold of the MLA layer; for every assignment that landed on a
    held expert its three matmuls; for every live position of an MLA layer the
    ABSORBED attention: every head's score over the row's 576 lanes and its
    weighted sum over the latent's 512."""
    h, heads, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    rank, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    dn, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    per_token = 2 * h * c["vocab_size"]
    for mla, dense in _layers(c):
        if mla:
            # W_ukv is not multiplied by the token: W_uk folds the query,
            # W_uv unfolds the weighted latents
            per_token += 2 * (mla_matmul_params(c) - rank * heads * (dn + dv))
            per_token += 2 * heads * rank * (dn + dv)
        else:
            per_token += 2 * kda_matmul_params(c)
            per_token += (2 * c["short_conv_kernel_size"] * 3 * heads * d
                          + 7 * heads * d * d)
        per_token += 2 * ffn_fixed_params(c, dense)
    return (tokens * per_token
            + 6 * h * c["moe_intermediate_size"] * assignments
            + 2 * heads * ((rank + dr) + rank) * latent_positions)


def traced_decode(rec: dict, rounds: float):
    """(:func:`decode_parts_bytes`, :func:`decode_least_flops`) of a
    recording's traced rounds, from the program's own counters over them; None
    where they hold nothing to read (a program without the latent counters, a
    run without decode rounds)."""
    import jax.numpy as jnp
    counters = rec["counters"]
    hit = counters.get("lm.traced_moe_experts_hit")
    rows = counters.get("lm.traced_state_rows")
    positions = counters.get("lm.traced_latent_positions")
    if not rounds or not hit or not rows or not positions:
        return None
    c = rec["config"]
    dtype_bytes = jnp.dtype(c["assumed"]["serve_dtype"]).itemsize
    return (decode_parts_bytes(c, dtype_bytes, rounds, hit, positions, rows),
            decode_least_flops(c, counters["lm.traced_active_slots"],
                               counters["lm.traced_moe_assignments"], positions))


def part_share_pct(rec: dict, part: str):
    """One part's share (%) of the least bytes the traced decode rounds had to
    move (:func:`decode_parts_bytes`' keys); None as :func:`traced_decode`."""
    traced = traced_decode(rec, rec["counters"].get("lm.traced_decode_rounds"))
    if traced is None:
        return None
    parts = traced[0]
    return parts[part] / sum(parts.values()) * 100.0


#: LMServingEngine's decode step in a device trace: jit__decode_fn
DECODE_MODULE = "decode_fn"


def traced_module(rec: dict):
    """(:func:`traced_decode` of the decode module's calls in the traced
    window, the module's device seconds); None as :func:`traced_decode`."""
    found = [m for name, m in rec["trace"]["modules"].items()
             if DECODE_MODULE in name]
    traced = traced_decode(rec, sum(m["calls"] for m in found))
    if traced is None:
        return None
    return traced, sum(m["device_s"] for m in found)
