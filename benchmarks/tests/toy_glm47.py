"""GLM-4.7-Flash at a toy size for CPU rehearsals and the tier-1 agreement
tests: hidden 64, 4 heads, every layer latent attention with a compressed query
(query rank 24, latent 24, 16 + 8 score lanes, 24 value lanes: score and value
widths differ, as the published 256 and 256 do not -- the harder case), a
leading dense layer and 3 routed ones (8 sigmoid-routed experts top-2, all
held, a shared expert) and the prediction module.  ``make_root`` builds a tree
of its own that holds the toy cell alone."""
import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**kw) -> dict:
    c = {
        "driver": "serve_glm47", "source": "toy", "reduced": [],
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 4, "num_nextn_predict_layers": 1,
        "vocab_size": 96, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
        "intermediate_size": 96, "first_k_dense_replace": 1,
        "q_lora_rank": 24, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 24, "rope_theta": 1000000,
        "partial_rotary_factor": 1, "tie_word_embeddings": False,
        "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "routed_scaling_factor": 1.8, "bigram_gain": 8.0,
        "mla_out_gain": 0.5,
        # float32 at toy size: a sound run reads gaps of a rounding or two at
        # a near-tie, the controls a thousand times more
        "assumed": {"serve_dtype": "float32"},
        "engine": {"slots": 4, "block_len": 4, "cache_len": 128,
                   "prefill_buckets": [8, 16], "num_blocks": 160,
                   "max_queue": 512, "self_draft_k": 1},
        "check": {"served_gap_max": 2e-4, "served_gap_mean": 2e-6,
                  "draft_gap_mean": 2e-6, "accept_gap": 0.02,
                  "latent_row_gap": 1e-4},
    }
    c.update(kw)
    return c


#: the cell's shape at a toy size: 2 shared prefixes of 16 tokens, tails of 4
#: and 8, outputs of 8 and 40, 6 clients over 4 slots
TOY_AGENTLOOP = {"kind": "closed", "clients": 6, "poll_s": 0.0005,
                 "sequence_len": 8, "order_seed": 40, "follow_s": 0,
                 "preroll_s": 1.0, "shared_prefixes": 2, "shared_prefix_len": 16,
                 "prompt_lens": [4, 8], "prompt_weights": [0.5, 0.5],
                 "output_lens": [8, 40], "output_weights": [0.5, 0.5]}


@contextlib.contextmanager
def _patched(owner, name, make):
    real = getattr(owner, name)
    setattr(owner, name, make(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def rope_dropped():
    """The check's control for the latent cache's CONTENT: every latent row
    (the main layers' and the module's) is cached without its rotated lanes."""
    from bigdl_tpu.models.transformer import TransformerLM

    def make(real):
        def changed(self, spec, bp, x, positions=None):
            q, row, gate = real(self, spec, bp, x, positions)
            return q, row.at[..., self.mla.kv_rank:].set(0), gate
        return changed
    return _patched(TransformerLM, "mla_inputs", make)


def hidden_off_by_one():
    """The check's control for the DRAFTER's input: the prediction module is
    fed the hidden state of the position BEFORE the one its pair states (the
    rows shifted by one more), in the prefills and the rounds alike: the served
    tokens stay right (drafts are verified), the drafts do not."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import TransformerLM

    def make(real):
        def changed(self, params, h, next_ids0):
            return real(self, params, jnp.roll(h, 1, axis=-2), next_ids0)
        return changed
    return _patched(TransformerLM, "mtp_embed", make)


def eh_hidden_dropped():
    """The check's control for ``eh_proj``: its hidden half is dropped (the
    module sees the next token's embedding alone)."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import TransformerLM

    def make(real):
        def changed(self, params, h, next_ids0):
            return real(self, params, jnp.zeros_like(h), next_ids0)
        return changed
    return _patched(TransformerLM, "mtp_embed", make)


CONTROLS = {"rope_dropped": rope_dropped, "hidden_off_by_one": hidden_off_by_one,
            "eh_hidden_dropped": eh_hidden_dropped}


def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/``: a copy of ``benchmarks/``
    and of the real file's entries, cut to the cell ``toy_glm47.agentloop``
    with the real cell's metrics."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dst, "configs", "toy-glm47.json"), "w") as f:
        json.dump(config(), f)
    with open(os.path.join(dst, "traffic", "toy_glm47.agentloop.json"), "w") as f:
        json.dump(TOY_AGENTLOOP, f)
    bench["configs"] = [{"name": "toy-glm47", "source": "toy", "reduced": [],
                         "why": "toy", "file": "benchmarks/configs/toy-glm47.json"}]
    bench["workloads"] = [{"name": "toy_glm47.agentloop", "config": "toy-glm47",
                           "traffic": "agentloop", "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=["toy_glm47.agentloop"])
                      if "workloads" in m else m for m in bench[key]
                      if "glm47.agentloop" in m.get("workloads",
                                                    ["glm47.agentloop"])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
