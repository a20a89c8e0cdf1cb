"""Ling-3.0-flash-VL's language model at a toy size for CPU rehearsals and the
tier-1 agreement tests: hidden 64, 4 heads; KDA layers of a 16 x 16 state with
a convolution of 4, a BOUNDED decay (lower bound -5) and full-rank decay and
gate projections; one MLA layer in six (latent 24, 16 + 8 score lanes, 16
value lanes, a gate a head); two leading dense layers and one period of six:
eight layers; 16 sigmoid-routed experts in 4 groups of which 2 stay, top-4,
one group (4 experts) held, a shared expert.  ``make_root`` builds a tree of
its own that holds the toy cell alone."""
import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**kw) -> dict:
    c = {
        "driver": "serve_ling3", "source": "toy", "reduced": [],
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 8, "vocab_size": 96,
        "max_position_embeddings": 96, "rms_norm_eps": 1e-6,
        "intermediate_size": 96, "first_k_dense_replace": 2,
        "layer_group_size": 6, "q_lora_rank": None, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 6000000, "rotary_dim": 8, "partial_rotary_factor": 0.5,
        "short_conv_kernel_size": 4, "no_kda_lora": True, "use_kda_lora": False,
        "kda_safe_gate": True, "kda_lower_bound": -5,
        "num_experts": 4, "experts_published": 16, "expert_share": [0, 4],
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
        "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        # float32 at toy size: a sound run reads gaps of a rounding or two at
        # a near-tie, the controls a thousand times more
        "assumed": {"serve_dtype": "float32"},
        "engine": {"slots": 4, "block_len": 4, "cache_len": 96,
                   "prefill_buckets": [16], "num_blocks": 100,
                   "max_queue": 512},
        "check": {"served_gap_max": 2e-4, "served_gap_mean": 2e-6,
                  "latent_row_gap": 1e-4},
    }
    c.update(kw)
    return c


#: as in the cell, no stream ends inside a run: 600 tokens are more than a CPU
#: serves one of four streams in the rehearsals' window of a second
TOY_LONGDECODE = {"kind": "closed", "clients": 4, "poll_s": 0.0005,
                  "sequence_len": 4, "order_seed": 35, "follow_s": 0,
                  "window_opens_at_token": 6,
                  "prompt_lens": [16, 40], "prompt_weights": [0.5, 0.5],
                  "output_lens": [600], "output_weights": [1.0]}
#: ... and the cell's engine holds them: 40 + 600 positions a slot
TOY_CELL = {"max_position_embeddings": 640,
            "engine": {"slots": 4, "block_len": 4, "cache_len": 640,
                       "prefill_buckets": [16], "num_blocks": 4 * 160 + 1,
                       "max_queue": 512}}


@contextlib.contextmanager
def _rows_changed(change):
    """While this is open, every program traced caches ``change(model, row)``
    for the row a latent layer caches -- ``[c ; k_r]``, what
    ``TransformerLM.mla_inputs`` hands out -- and attends that (the prefills'
    expanded form and the decode step's absorbed one alike).  The program has
    no such option: this wraps the one method (the reference is plain
    ``jax.numpy``)."""
    from bigdl_tpu.models.transformer import TransformerLM
    real = TransformerLM.mla_inputs

    def changed(self, spec, bp, x, positions=None):
        q, row, gate = real(self, spec, bp, x, positions)
        return q, change(self, row), gate

    TransformerLM.mla_inputs = changed
    try:
        yield
    finally:
        TransformerLM.mla_inputs = real


def latent_rounded(dtype: str = "float8_e4m3fn"):
    """The check's control for the latent cache's PRECISION: the cached row
    rounded to ``dtype``, a narrower type than the bfloat16 the configuration
    states.  ``reduce_precision``, not a pair of casts: the TPU compiler drops
    a round trip through a narrower type as excess precision."""
    import jax.numpy as jnp
    from jax import lax
    fi = jnp.finfo(jnp.dtype(dtype))
    return _rows_changed(
        lambda model, row: lax.reduce_precision(row, fi.nexp, fi.nmant))


def rope_dropped():
    """The check's control for the latent cache's CONTENT (ISSUE 35's second
    form of it): the row's rotated lanes ``k_r`` are cached as zeros, so every
    score loses its ``q_r . k_r`` term."""
    return _rows_changed(
        lambda model, row: row.at[..., model.mla.kv_rank:].set(0))


def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/``: a copy of ``benchmarks/``
    and of the real file's entries, cut to the cell ``toy_ling3.longdecode``
    with the real cell's metrics."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dst, "configs", "toy-ling3.json"), "w") as f:
        json.dump(config(**TOY_CELL), f)
    with open(os.path.join(dst, "traffic", "toy_ling3.longdecode.json"), "w") as f:
        json.dump(TOY_LONGDECODE, f)
    bench["configs"] = [{"name": "toy-ling3", "source": "toy", "reduced": [],
                         "why": "toy", "file": "benchmarks/configs/toy-ling3.json"}]
    bench["workloads"] = [{"name": "toy_ling3.longdecode", "config": "toy-ling3",
                           "traffic": "longdecode", "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=["toy_ling3.longdecode"])
                      if "workloads" in m else m for m in bench[key]
                      if "ling3.longdecode" in m.get("workloads",
                                                     ["ling3.longdecode"])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
