"""MiMo-V2-Flash at a toy size for CPU rehearsals and the tier-1 agreement
tests: hidden 64, 4 query heads of 24 key and 16 value lanes (8 of a key head
rotated), 2 K/V heads on sliding layers (window 8, a sink) and 1 on full ones,
16 routed experts top-4 of which 2 are held (share 0 of 8), no shared expert,
the published pattern's first seven layers (full + dense, then sliding x 4,
full, sliding, all routed): two classes of blocks, blocks of 4.  The CONTROLS
of the comparison that decides ``correct`` live here, each a context manager
over the program (the program has no option for any of them)."""
import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**kw) -> dict:
    c = {
        "driver": "serve_mimo_v2", "source": "toy", "reduced": [],
        "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 128, "max_position_embeddings": 96,
        "num_attention_heads": 4, "head_dim": 24, "num_hidden_layers": 7,
        "num_key_value_heads": 1, "layernorm_epsilon": 1e-5,
        "rope_theta": 5000000, "tie_word_embeddings": False, "vocab_size": 96,
        "partial_rotary_factor": 0.334, "sliding_window": 8,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 16,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False,
        "moe_layer_freq": [0] + [1] * 11, "moe_intermediate_size": 32,
        "n_routed_experts": 2, "experts_published": 16, "expert_share": [0, 8],
        "n_shared_experts": None, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": None,
        "swa_num_key_value_heads": 2,
        # float32 at toy size: a sound run reads gaps of a rounding or two at
        # a near-tie, the controls a thousand times more
        "assumed": {"serve_dtype": "float32", "attention_impl": "xla"},
        "engine": {"slots": 4, "block_len": 4, "cache_len": 96,
                   "prefill_buckets": [8, 16, 32], "num_blocks": [120, 64],
                   "max_queue": 512},
        "check": {"served_gap_max": 2e-4, "served_gap_mean": 2e-6},
    }
    c.update(kw)
    return c


TOY_MIXED = {
    "kind": "closed", "clients": 6, "poll_s": 0.001,
    "long": {"kind": "closed", "clients": 2, "poll_s": 0.001, "sequence_len": 2,
             "prompt_lens": [40, 56], "prompt_weights": [0.5, 0.5],
             "output_lens": [560], "output_weights": [1.0], "order_seed": 42},
    "short": {"kind": "closed", "clients": 4, "poll_s": 0.001,
              "sequence_len": 64, "prompt_lens": [8, 16],
              "prompt_weights": [0.5, 0.5], "output_lens": [6, 12],
              "output_weights": [0.5, 0.5], "order_seed": 42},
    "window_opens_at_token": 4, "preroll_s": 0.5, "follow_s": 0}


# -- the controls: each must be refused by the comparison ---------------------------
@contextlib.contextmanager
def sink_dropped():
    """The sink left out of every softmax the program computes."""
    from bigdl_tpu.models.transformer import TransformerLM
    plain = TransformerLM.layer_sink
    TransformerLM.layer_sink = lambda self, spec, bp: None
    try:
        yield
    finally:
        TransformerLM.layer_sink = plain


@contextlib.contextmanager
def release_early():
    """The windowed class lets go ONE BLOCK EARLY: a sliding layer reads the
    scratch block where a key should be."""
    from bigdl_tpu.serving.kvcache.blocks import BlockPool
    plain = BlockPool.advance

    def early(self, chain, marks, pos, upto):
        return plain(self, chain, marks, pos + self.block_len, upto)

    BlockPool.advance = early
    try:
        yield
    finally:
        BlockPool.advance = plain


@contextlib.contextmanager
def values_unscaled():
    """``attention_value_scale`` not applied."""
    from bigdl_tpu.models.transformer import TransformerLM
    plain = TransformerLM.layer_qkv

    def unscaled(self, spec, bp, x, positions=None):
        scale, self.value_scale = self.value_scale, 1.0
        try:
            return plain(self, spec, bp, x, positions)
        finally:
            self.value_scale = scale

    TransformerLM.layer_qkv = unscaled
    try:
        yield
    finally:
        TransformerLM.layer_qkv = plain


@contextlib.contextmanager
def kv_int8():
    """K and V rounded to int8 a (position, head) row, absmax scales, before
    they are cached or read (the int8 pool's own arithmetic,
    ``generate._kv_quantize_rows``: a pool of several classes carries no
    scale arenas yet)."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.models.transformer.generate import _kv_quantize_rows
    plain = TransformerLM.layer_qkv

    def rounded(self, spec, bp, x, positions=None):
        q, k, v, gate = plain(self, spec, bp, x, positions)

        def through_int8(a):
            q8, s = _kv_quantize_rows(a)
            return (q8.astype(jnp.float32) * s[..., None]).astype(a.dtype)

        return q, through_int8(k), through_int8(v), gate

    TransformerLM.layer_qkv = rounded
    try:
        yield
    finally:
        TransformerLM.layer_qkv = plain


CONTROLS = {"sink_dropped": sink_dropped, "release_early": release_early,
            "values_unscaled": values_unscaled, "kv8": kv_int8}


def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/``: a copy of ``benchmarks/``
    and of the real file's entries, cut to the cell ``toy_mimo_v2.mixedqueue``
    with the real cell's metrics."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    # (contexts long enough that, as in the cell, no long stream ends inside
    # a run: 56 + 560 positions, tables of 160 blocks)
    cell = config(max_position_embeddings=640)
    cell["engine"] = dict(cell["engine"], cache_len=640, num_blocks=[700, 64])
    with open(os.path.join(dst, "configs", "toy-mimo-v2.json"), "w") as f:
        json.dump(cell, f)
    with open(os.path.join(dst, "traffic", "toy_mimo_v2.mixedqueue.json"), "w") as f:
        json.dump(TOY_MIXED, f)
    bench["configs"] = [{"name": "toy-mimo-v2", "source": "toy", "reduced": [],
                         "why": "toy", "file": "benchmarks/configs/toy-mimo-v2.json"}]
    bench["workloads"] = [{"name": "toy_mimo_v2.mixedqueue", "config": "toy-mimo-v2",
                           "traffic": "mixedqueue", "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=["toy_mimo_v2.mixedqueue"])
                      if "workloads" in m else m for m in bench[key]
                      if "mimo_v2.mixedqueue" in m.get("workloads",
                                                       ["mimo_v2.mixedqueue"])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
