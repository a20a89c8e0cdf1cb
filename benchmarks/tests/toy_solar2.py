"""Solar-Open2-250B at a toy size for CPU rehearsals and the tier-1 agreement
tests: hidden 64, 8 query heads of 16 over 2 K/V heads on the softmax layers
(no position encoding, an elementwise gate), KDA layers of 4 heads of 16 x 16
state with a convolution of 4, 16 sigmoid-routed experts top-3 of which 2 are
held (one share of eight) and a shared expert; two whole periods (softmax,
KDA, KDA, KDA): eight layers, one group of the layer plan.  ``make_root``
builds a tree of its own that holds the toy cell alone."""
import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**kw) -> dict:
    c = {
        "driver": "serve_solar2", "source": "toy", "reduced": [],
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 8,
        "num_key_value_heads": 2, "num_hidden_layers": 8, "vocab_size": 96,
        "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 4, "num_kv_heads": None},
        "gqa_interval": 3, "gqa_layers": [0, 4, 8, 12], "use_rope": False,
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
        "n_routed_experts": 2, "experts_published": 16, "expert_share": [0, 8],
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "tie_word_embeddings": False,
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


TOY_BACKLOG = {"kind": "closed", "clients": 6, "poll_s": 0.0005,
               "sequence_len": 60, "order_seed": 7, "follow_s": 0,
               "preroll_s": 0.5,
               "prompt_lens": [8, 16, 32], "prompt_weights": [0.3, 0.4, 0.3],
               "output_lens": [6, 12], "output_weights": [0.5, 0.5]}

#: the check's controls, as ``run_cell``'s ``config_update``: the program's
#: int8 KV blocks (the one K/V layer a period).  The other two are
#: :func:`state_rounded` and an altered token (tests/test_solar2_cell.py).
KV8 = {"engine": {"kv_quant": "int8", "decode_attn": "gather"}}


@contextlib.contextmanager
def state_rounded(dtype: str = "bfloat16"):
    """The check's control for the recurrent state: while this is open, every
    program traced keeps the KDA layers' state in ``dtype``, a narrower type
    than the float32 the configuration states: what ``kda_step`` and
    ``kda_chunked`` take and hand back is rounded to it.  The program has no
    such option: this wraps the two functions of ``bigdl_tpu.nn.kda`` (the
    model's layers look them up at trace time; the reference is plain
    ``jax.numpy``).  ``reduce_precision``, not a pair of casts: the TPU
    compiler drops a round trip through a narrower type as excess precision."""
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.nn import kda
    fi = jnp.finfo(jnp.dtype(dtype))
    rounded = lambda s: lax.reduce_precision(s, fi.nexp, fi.nmant)  # noqa: E731
    step, chunked = kda.kda_step, kda.kda_chunked

    def step_rounded(q, k, v, g, beta, state):
        o, state = step(q, k, v, g, beta, rounded(state))
        return o, rounded(state)

    def chunked_rounded(q, k, v, g, beta, state=None, valid=None, **kw):
        o, state = chunked(q, k, v, g, beta,
                           None if state is None else rounded(state), valid, **kw)
        return o, rounded(state)

    kda.kda_step, kda.kda_chunked = step_rounded, chunked_rounded
    try:
        yield
    finally:
        kda.kda_step, kda.kda_chunked = step, chunked


def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/``: a copy of ``benchmarks/``
    and of the real file's entries, cut to the cell ``toy_solar2.backlog``
    with the real cell's metrics."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dst, "configs", "toy-solar2.json"), "w") as f:
        json.dump(config(), f)
    with open(os.path.join(dst, "traffic", "toy_solar2.backlog.json"), "w") as f:
        json.dump(TOY_BACKLOG, f)
    bench["configs"] = [{"name": "toy-solar2", "source": "toy", "reduced": [],
                         "why": "toy", "file": "benchmarks/configs/toy-solar2.json"}]
    bench["workloads"] = [{"name": "toy_solar2.backlog", "config": "toy-solar2",
                           "traffic": "backlog", "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=["toy_solar2.backlog"])
                      if "workloads" in m else m for m in bench[key]
                      if "solar2.backlog" in m.get("workloads",
                                                   ["solar2.backlog"])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
