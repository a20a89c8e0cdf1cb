"""Operations and bytes GLM-4.7-Flash's self-drafting round and its prefills
need, from the configuration file's shapes alone (every expert and the whole
vocabulary are held here).  Hand-worked lines are in PERF.md section 3;
``benchmarks/tests/test_costs_glm47.py`` holds the functions to those numbers."""


def mla_matmul_params(c: dict) -> int:
    """W_qa, W_qb, W_dkv, W_ukv, Wo."""
    h, heads, rank, qr = (c["hidden_size"], c["num_attention_heads"],
                          c["kv_lora_rank"], c["q_lora_rank"])
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return (h * qr + qr * heads * (dn + dr) + h * (rank + dr)
            + rank * heads * (dn + dv) + heads * dv * h)


def mla_params(c: dict) -> int:
    """... and the query's and the latent's norms."""
    return mla_matmul_params(c) + c["q_lora_rank"] + c["kv_lora_rank"]


def ffn_fixed_params(c: dict, dense: bool) -> int:
    """What a block's feed-forward half reads whatever the batch, without its
    two norm vectors: a dense layer's SwiGLU, or a routed layer's router and
    shared expert."""
    h = c["hidden_size"]
    if dense:
        return 3 * h * c["intermediate_size"]
    return (h * c["n_routed_experts"]
            + 3 * h * c["moe_intermediate_size"] * c["n_shared_experts"])


def block_fixed_params(c: dict, dense: bool) -> int:
    """A block outside its routed experts: mixer, fixed feed-forward part, two
    norm vectors."""
    return mla_params(c) + ffn_fixed_params(c, dense) + 2 * c["hidden_size"]


def head_bytes(c: dict, dtype_bytes: int) -> int:
    """The untied head, whole: read once for the verify rows' logits and ONCE
    MORE for the draft's where the prediction module ran."""
    return c["hidden_size"] * c["vocab_size"] * dtype_bytes


def expert_bytes(c: dict, dtype_bytes: int) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def round_fixed_bytes(c: dict, dtype_bytes: int, drafted: bool = True) -> int:
    """Bytes of weights one round reads whatever the batch, the head apart: the
    main blocks outside their experts and the final norm and, where the
    prediction module ran, its block outside its experts, ``eh_proj`` and its
    three norms.  Not the embedding (a row a token), not the routers' selection
    bias (256 B a layer)."""
    h, dense = c["hidden_size"], c["first_k_dense_replace"]
    total = h + sum(block_fixed_params(c, l < dense)
                    for l in range(c["num_hidden_layers"]))
    if drafted:
        total += block_fixed_params(c, False) + 2 * h * h + 3 * h
    return total * dtype_bytes


def latent_bytes_per_position_layer(c: dict, dtype_bytes: int) -> int:
    """The one row a cached position holds in one arena layer, as the model
    states it (576 lanes; the arena pads a row to 640: no part of the least)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * dtype_bytes


def round_parts_bytes(c: dict, dtype_bytes: int, rounds: float,
                      draft_rounds: float, experts_hit: int,
                      latent_positions: int) -> dict:
    """The least a set of rounds has to move, by part: the weights outside the
    experts and the head once a round (the module's too in a round that
    drafted); the head once a round and once more where the module ran; an
    expert's matrices for every (block, round, expert) hit, by the program's
    own count; ONE row for every live position of every arena layer read
    (``latent_positions``: the program's count over ALL arena layers, the
    module's too)."""
    plain = rounds - draft_rounds
    return {"fixed": (draft_rounds * round_fixed_bytes(c, dtype_bytes, True)
                      + plain * round_fixed_bytes(c, dtype_bytes, False)),
            "head": (rounds + draft_rounds) * head_bytes(c, dtype_bytes),
            "experts": experts_hit * expert_bytes(c, dtype_bytes),
            "latent": latent_positions * latent_bytes_per_position_layer(
                c, dtype_bytes)}


def prefill_flops(c: dict, tokens: int, pairs: float, chunks: int) -> float:
    """Multiply-adds x 2 a set of prefill programs needs, by TRUE lengths: for
    every prompt token computed (``tokens``: matched prefixes are not) the
    matrix products of every main block outside the routed experts, its 4
    experts (every one is held: top-k assignments a token and routed layer),
    and the prediction module's ROWS (``eh_proj`` and its block's
    down-projection: a prefill runs nothing else of the module); for every
    (query, key) pair of causal attention (``pairs``: a token against its
    matched prefix and the tokens before it in its own prompt) every head's
    EXPANDED score over 256 lanes and weighted sum over 256; the head for ONE
    position a program (``chunks``)."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    dense = c["first_k_dense_replace"]
    per_token = 0
    for l in range(c["num_hidden_layers"]):
        per_token += 2 * (mla_matmul_params(c) + ffn_fixed_params(c, l < dense))
        if l >= dense:
            per_token += (6 * h * c["moe_intermediate_size"]
                          * c["num_experts_per_tok"])
    if c["num_nextn_predict_layers"]:
        per_token += 2 * (2 * h * h + h * (c["kv_lora_rank"] + dr))
    attention = 2 * heads * ((dn + dr) + dv) * c["num_hidden_layers"]
    return (tokens * per_token + pairs * attention
            + chunks * 2 * h * c["vocab_size"])


#: LMServingEngine's self-drafting round and its plain decode step in a device
#: trace: jit__selfdraft_fn, jit__decode_fn; its prefills: jit__prefill_fn,
#: jit__prefix_prefill_fn
ROUND_MODULES = ("selfdraft_fn", "decode_fn")
PREFILL_MODULES = ("prefill_fn",)


def traced_rounds(rec: dict):
    """:func:`round_parts_bytes` of a recording's traced rounds, from the
    program's own counters over them (the args of its ``lm/verify_step``
    spans); None where they hold nothing to read (a program without the
    spans, a run without rounds)."""
    import jax.numpy as jnp
    counters = rec["counters"]
    rounds = counters.get("lm.traced_rounds")
    hit = counters.get("lm.traced_moe_experts_hit")
    positions = counters.get("lm.traced_latent_positions")
    if not rounds or not hit or not positions:
        return None
    c = rec["config"]
    dtype_bytes = jnp.dtype(c["assumed"]["serve_dtype"]).itemsize
    return round_parts_bytes(c, dtype_bytes, rounds,
                             counters.get("lm.traced_draft_rounds", 0), hit,
                             positions)


def modules_device_s(rec: dict, names) -> float:
    return sum(m["device_s"] for name, m in rec["trace"]["modules"].items()
               if any(n in name for n in names))
