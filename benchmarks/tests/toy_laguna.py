"""Laguna-S-2.1 at a toy size for CPU rehearsals and the tier-1 agreement
tests: hidden 64, 2 K/V heads of 16, 6 query heads on sliding layers (window
8) and 4 on full ones (half of each head rotated, YaRN), 16 routed experts
top-3 of which 8 are held, a shared expert, one dense layer and two whole
periods (sliding, sliding, sliding, full): nine layers, two groups of the
layer plan.  ``make_root`` builds a tree of its own that holds the toy cell
alone (``toy.make_root`` knows PR 23's two cells and no other)."""
import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**kw) -> dict:
    c = {
        "driver": "serve_laguna", "source": "toy", "reduced": [],
        "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
        "num_attention_heads": 4, "num_hidden_layers": 9, "vocab_size": 96,
        "intermediate_size": 128, "max_position_embeddings": 64,
        "attention_bias": False, "rms_norm_eps": 1e-6, "num_experts": 8,
        "experts_published": 16, "expert_share": [0, 2],
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
        "mlp_only_layers": [0], "tie_word_embeddings": False,
        "gating": "per-head", "sliding_window": 8,
        "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.1386,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 3,
        "mlp_layer_types": ["dense"] + ["sparse"] * 11,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 3,
        # float32 at toy size: a sound run reads gaps of a rounding or two at
        # a near-tie, the controls a thousand times more
        "assumed": {"serve_dtype": "float32", "attention_impl": "xla"},
        "engine": {"slots": 4, "block_len": 4, "cache_len": 64,
                   "prefill_buckets": [8, 16, 32], "num_blocks": 96,
                   "max_queue": 512},
        "check": {"served_gap_max": 2e-4, "served_gap_mean": 2e-6},
    }
    c.update(kw)
    return c


TOY_STEADY = {"kind": "poisson", "rate_rps": 8.0, "follow_s": 10,
              "prompt_lens": [8, 16, 32], "prompt_weights": [0.3, 0.4, 0.3],
              "output_lens": [6, 12], "output_weights": [0.5, 0.5]}

#: the check's first control, as ``run_cell``'s ``config_update``: the
#: program's int8 KV blocks.  The second is :func:`experts_rounded`.
KV8 = {"engine": {"kv_quant": "int8", "decode_attn": "gather"}}


@contextlib.contextmanager
def experts_rounded(dtype: str):
    """The check's second control: while this is open, every program traced
    rounds the activations of its grouped expert matmuls to ``dtype``, a
    narrower type than the configuration states.  The program has no such
    option: this wraps the ``lax.ragged_dot`` that
    ``bigdl_tpu.parallel.expert.grouped_swiglu`` calls (nothing else in the
    repo calls it; the reference is plain ``jax.numpy``).
    ``reduce_precision``, not a pair of casts: the TPU compiler drops a round
    trip through a narrower type as excess precision."""
    import jax.numpy as jnp
    from jax import lax
    fi = jnp.finfo(jnp.dtype(dtype))
    plain = lax.ragged_dot

    def rounded(a, w, sizes, **kw):
        return plain(lax.reduce_precision(a, fi.nexp, fi.nmant), w, sizes, **kw)

    lax.ragged_dot = rounded
    try:
        yield
    finally:
        lax.ragged_dot = plain


def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/``: a copy of ``benchmarks/``
    and of the real file's entries, cut to the cell ``toy_laguna.steady``
    with the real cell's metrics."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dst, "configs", "toy-laguna.json"), "w") as f:
        json.dump(config(), f)
    with open(os.path.join(dst, "traffic", "toy_laguna.steady.json"), "w") as f:
        json.dump(TOY_STEADY, f)
    bench["configs"] = [{"name": "toy-laguna", "source": "toy", "reduced": [],
                         "why": "toy", "file": "benchmarks/configs/toy-laguna.json"}]
    bench["workloads"] = [{"name": "toy_laguna.steady", "config": "toy-laguna",
                           "traffic": "steady", "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=["toy_laguna.steady"])
                      if "workloads" in m else m for m in bench[key]
                      if "laguna_s.steady" in m.get("workloads",
                                                    ["laguna_s.steady"])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
