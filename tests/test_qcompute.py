"""True int8-compute acceptance: kernels, activation quantization, the
int8-compute drafter, and int8 KV storage.

The subsystem's central claim is split into the two properties it
actually rests on:

* **kernel parity** — ``qmatmul_i8`` (int8 x int8 -> int32 -> one f32
  rescale) tracks the f32 matmul to quantization noise, and the argmax
  (what greedy decoding reads) agrees;
* **replay exactness** — the spec engine's emitted stream is the
  TARGET's trajectory whatever kernels the drafter runs, so an
  int8-compute drafter keeps streams bit-exact BY CONSTRUCTION while
  its acceptance stays above the demotion threshold.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from bigdl_tpu.models.transformer import TransformerLM  # noqa: E402
from bigdl_tpu.models.transformer.generate import generate  # noqa: E402
from bigdl_tpu.quant import (ActCalibrator, QuantPolicy,  # noqa: E402
                             attach_act_scales, dequantize_entry,
                             fp8_supported, is_qtensor, params_compute_tag,
                             qconv, qconv_i8, qlinear, qlinear_i8, qmatmul,
                             qmatmul_i8, quantize_array, quantize_per_token,
                             set_compute_mode)
from bigdl_tpu.serving import LMServingEngine, SpecConfig  # noqa: E402
from bigdl_tpu.serving.kvcache.blocks import BlockPool  # noqa: E402

RNG = np.random.RandomState(11)


def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=64, seed=0):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers, max_len=max_len,
                         pos_encoding="rope").build(seed=seed)


def _ref(model, prompt, max_new, temperature=0.0, seed=None):
    kw = dict(temperature=temperature)
    if seed is not None:
        kw["rng"] = jax.random.PRNGKey(seed)
    return np.asarray(generate(model, model.params,
                               np.asarray(prompt)[None].astype(np.int32),
                               max_new, **kw))[0]


# --------------------------------------------------------------------------- #
# kernels: int8 x int8 -> int32 -> f32 rescale                                #
# --------------------------------------------------------------------------- #

def test_qmatmul_i8_tracks_f32_and_argmax_agrees():
    x = jnp.asarray(RNG.randn(8, 64).astype(np.float32))
    w = RNG.randn(64, 96).astype(np.float32)
    qw = quantize_array(w, (0,), compute="int8")
    got = np.asarray(qmatmul_i8(x, qw))
    ref = np.asarray(x) @ w
    # two int8 operands -> quantization noise from both sides; scale-
    # relative tolerance, plus the decision greedy decoding actually
    # takes must agree on (almost) every row
    assert np.max(np.abs(got - ref)) < 0.05 * np.max(np.abs(ref))
    agree = np.mean(np.argmax(got, -1) == np.argmax(ref, -1))
    assert agree >= 0.875


def test_qmatmul_dispatches_by_compute_mode():
    x = jnp.asarray(RNG.randn(4, 32).astype(np.float32))
    w = RNG.randn(32, 48).astype(np.float32)
    ref = np.asarray(x) @ w
    # plain array passes through; dequant and int8 both track f32
    assert np.allclose(np.asarray(qmatmul(x, jnp.asarray(w))), ref,
                       atol=1e-5)
    dq = np.asarray(qmatmul(x, quantize_array(w, (0,))))
    i8 = np.asarray(qmatmul(x, quantize_array(w, (0,), compute="int8")))
    tol = 0.05 * np.max(np.abs(ref))
    assert np.max(np.abs(dq - ref)) < tol
    assert np.max(np.abs(i8 - ref)) < tol
    # int8 result differs from dequant (it really ran the other kernel)
    assert not np.array_equal(i8, dq)


def test_qlinear_i8_matches_dequant_regime_to_tolerance():
    x = jnp.asarray(RNG.randn(5, 40).astype(np.float32))
    w = RNG.randn(24, 40).astype(np.float32)  # Linear (out, in)
    b = jnp.asarray(RNG.randn(24).astype(np.float32))
    ref = np.asarray(qlinear(x, quantize_array(w, (-1,)), b))
    got = np.asarray(qlinear_i8(x, quantize_array(w, (-1,),
                                                  compute="int8"), b))
    assert np.max(np.abs(got - ref)) < 0.05 * max(np.max(np.abs(ref)), 1.0)


def test_qconv_i8_matches_dequant_regime_to_tolerance():
    x = jnp.asarray(RNG.randn(2, 3, 8, 8).astype(np.float32))  # NCHW
    w = RNG.randn(4, 3, 3, 3).astype(np.float32)               # OIHW
    kw = dict(window_strides=(1, 1), padding="SAME",
              dimension_numbers=("NCHW", "OIHW", "NCHW"))
    ref = np.asarray(qconv(x, quantize_array(w, (1, 2, 3)), **kw))
    got = np.asarray(qconv_i8(x, quantize_array(w, (1, 2, 3),
                                                compute="int8"), **kw))
    assert np.max(np.abs(got - ref)) < 0.08 * max(np.max(np.abs(ref)), 1.0)


# --------------------------------------------------------------------------- #
# activation quantization + calibration                                       #
# --------------------------------------------------------------------------- #

def test_quantize_per_token_roundtrip_and_static_scale():
    x = jnp.asarray(RNG.randn(6, 32).astype(np.float32) * 3.0)
    q, s = quantize_per_token(x)
    assert q.dtype == jnp.int8 and s.shape == (6, 1)
    rt = np.asarray(q, np.float32) * np.asarray(s)
    assert np.max(np.abs(rt - np.asarray(x))) <= np.max(np.asarray(s))
    # calibrated static scale skips the dynamic reduction but keeps the
    # same (q * s ~= x) contract
    q2, s2 = quantize_per_token(x, scale=float(np.abs(x).max()) / 127.0)
    assert np.unique(np.asarray(s2)).size == 1
    rt2 = np.asarray(q2, np.float32) * np.asarray(s2)
    assert np.max(np.abs(rt2 - np.asarray(x))) <= float(np.asarray(s2)[0, 0])


def test_act_calibrator_freezes_absmax_scales_onto_leaves():
    cal = ActCalibrator()
    for batch in (np.ones((2, 4)) * 2.0, np.ones((2, 4)) * 5.0):
        cal.observe("blocks/attn/wq", batch)
    scales = cal.scales()
    assert scales["blocks/attn/wq"] == pytest.approx(5.0 / 127.0)
    assert cal.describe()["blocks/attn/wq"]["batches"] == 2
    params = {"blocks": {"attn": {"wq": quantize_array(
        RNG.randn(8, 8).astype(np.float32), (0,), compute="int8")}}}
    pinned = attach_act_scales(params, scales)
    qt = pinned["blocks"]["attn"]["wq"]
    assert qt.act_scale == pytest.approx(5.0 / 127.0)
    # unmatched paths are a silent no-op by design
    attach_act_scales(params, {"nope/nothing": 1.0})


def test_fp8_gates_on_device_kind():
    from bigdl_tpu.quant.activations import (FP8_DTYPE,
                                             quantize_per_token_fp8)
    if jax.devices()[0].platform == "cpu":
        assert not fp8_supported()
        with pytest.raises(NotImplementedError):
            quantize_per_token_fp8(jnp.ones((2, 4)))
    if FP8_DTYPE is not None:
        q, s = quantize_per_token_fp8(jnp.ones((2, 4)), force=True)
        assert q.dtype == FP8_DTYPE and s.shape == (2, 1)


# --------------------------------------------------------------------------- #
# policy / transform plumbing                                                 #
# --------------------------------------------------------------------------- #

def test_quant_policy_validates_compute():
    with pytest.raises(ValueError):
        QuantPolicy("int8", compute="bf16")
    for mode in ("dequant", "int8"):
        assert QuantPolicy("int8", compute=mode).compute == mode


def test_quantize_reports_compute_mode_and_overflow_risk():
    model = _lm()
    qlm = model.quantize("int8", compute="int8")
    rep = qlm.quant_report
    assert rep["compute_mode"] == "int8"
    assert params_compute_tag(qlm.params) == "int8"
    risks = rep["per_layer_overflow_risk"]
    assert risks and all(0.0 <= r < 1.0 for r in risks.values())
    assert rep["overflow_risk"] == pytest.approx(max(risks.values()))
    from bigdl_tpu.obs import get_registry
    gauge = get_registry().get("quant/overflow_risk")
    assert gauge is not None
    assert gauge.snapshot()["value"] == pytest.approx(rep["overflow_risk"])


def test_dequantize_entry_keeps_compute_leaves():
    model = _lm()
    entry_dq = dequantize_entry(model.quantize("int8").params)
    entry_i8 = dequantize_entry(
        model.quantize("int8", compute="int8").params)
    assert not any(is_qtensor(v)
                   for v in entry_dq["blocks"]["attn"].values())
    assert is_qtensor(entry_i8["blocks"]["attn"]["wq"])
    # and set_compute_mode retags without re-quantizing
    retag = set_compute_mode(model.quantize("int8").params, "int8")
    assert params_compute_tag(retag) == "int8"


@pytest.mark.parametrize("site", ["QTensor", "QuantPolicy", "quantize",
                                  "set_compute_mode", "SpecConfig"])
def test_compute_auto_is_refused(site):
    """There is no ``"auto"`` compute mode: a leaf's ``compute`` IS the
    recipe its kernel runs (``qmatmul`` and the layers' kernels read it), so
    every place that takes one names the recipes alone."""
    w = RNG.randn(32, 48).astype(np.float32)
    with pytest.raises(ValueError, match="compute"):
        if site == "QTensor":
            quantize_array(w, (0,), compute="auto")
        elif site == "QuantPolicy":
            QuantPolicy("int8", compute="auto")
        elif site == "quantize":
            _lm().quantize("int8", compute="auto")
        elif site == "set_compute_mode":
            set_compute_mode(_lm().quantize("int8").params, "auto")
        else:
            SpecConfig(drafter_compute="auto")


# --------------------------------------------------------------------------- #
# tier-1: the int8-compute drafter keeps replay streams bit-exact             #
# --------------------------------------------------------------------------- #

def test_spec_int8_compute_drafter_bitexact_with_radix_sharing():
    """The acceptance criterion: drafter runs TRUE int8 compute, radix
    prefix sharing on (same base prompt served repeatedly, greedy AND
    sampled), and every stream is still the offline f32 trajectory
    bit-exact — while the drafter's acceptance EMA stays above the
    demotion threshold (its numerics are good enough to speculate
    with, not just safe)."""
    model = _lm()
    cfg = SpecConfig(k=3, drafter_compute="int8")
    eng = LMServingEngine(model, slots=4, cache_len=48, block_len=4,
                          max_new_tokens=8, prefill_buckets=(8, 16),
                          spec=cfg)
    eng.warmup()
    try:
        rng = np.random.default_rng(2)
        base = rng.integers(1, 32, size=8).astype(np.int32)
        cases = [(base, 0.0, None), (base.copy(), 0.7, 3),
                 (np.concatenate([base, [5, 7]]).astype(np.int32),
                  0.9, 4)]
        streams = [eng.submit(p, max_new_tokens=8, temperature=t,
                              rng=s) for p, t, s in cases]
        for (p, t, s), stm in zip(cases, streams):
            np.testing.assert_array_equal(
                stm.result(timeout=60), _ref(model, p, 8, t, s))
        assert eng.radix.hit_rate() > 0.0
        spec = eng.stats()["spec"]
        assert spec["compute_mode"] == "int8"
        assert spec["drafted"] > 0
        assert spec["demotions"] == 0
        assert spec["acceptance_rate"] > cfg.demote_below
        assert 0.0 <= spec["overflow_risk"] < 1.0
        assert eng.draft.compute_mode == "int8"
    finally:
        eng.close()


def test_spec_config_validates_drafter_compute():
    with pytest.raises(ValueError):
        SpecConfig(drafter_compute="bf16")
    assert SpecConfig(drafter_compute="int8").describe()[
        "drafter_compute"] == "int8"


# --------------------------------------------------------------------------- #
# int8 KV storage mode                                                        #
# --------------------------------------------------------------------------- #

def test_blockpool_int8_arenas_and_migration_gate():
    pool = BlockPool(n_layers=1, n_heads=2, head_dim=8, block_len=4,
                     num_blocks=6, dtype=np.float32, kv_quant="int8")
    assert pool.k.dtype == jnp.int8 and pool.ks.dtype == jnp.float32
    # a block's 4 x 2 scales in one lane-padded row
    assert pool.ks.shape == pool.scale_shape == (1, 6, 128)
    assert pool.stats()["kv_quant"] == "int8"
    # scale arenas are accounted, and the int8 arenas beat the f32
    # pool's footprint despite them
    plain = BlockPool(n_layers=1, n_heads=2, head_dim=8, block_len=4,
                      num_blocks=6, dtype=np.float32)
    assert pool.arena_bytes < plain.arena_bytes
    assert plain.stats()["kv_quant"] == "none"
    # int8 chains DO export/adopt (PR 16 host-tier demotion rides
    # this), but the scales travel atomically: a wire payload without
    # them cannot dequantize and must be refused
    wire = pool.export_chain([1])
    assert wire["k"].dtype == np.int8 and "ks" in wire and "vs" in wire
    with pytest.raises(ValueError):
        pool.adopt_chain(wire["k"], wire["v"])
    with pytest.raises(ValueError):
        BlockPool(n_layers=1, n_heads=2, head_dim=8, block_len=4,
                  num_blocks=6, kv_quant="int4")


def test_engine_kv_quant_int8_stream_and_gates():
    model = _lm(seed=3)
    eng = LMServingEngine(model, slots=2, cache_len=48, block_len=4,
                          max_new_tokens=8, prefill_buckets=(8,),
                          kv_quant="int8")
    eng.warmup()
    try:
        assert eng.pool.stats()["kv_quant"] == "int8"
        assert eng.decode_attn == "gather"
        p = np.asarray([3, 9, 14, 2, 6, 1, 8, 4], np.int32)
        out = eng.submit(p, max_new_tokens=8).result(timeout=60)
        # int8 KV is lossy, but per-(position, head) scales keep this
        # small model's greedy path on the f32 trajectory (pinned
        # seeds; deterministic on the tier-1 CPU platform)
        np.testing.assert_array_equal(out, _ref(model, p, 8))
    finally:
        eng.close()
    # explicit paged_kernel is incompatible with dequant-in-gather
    with pytest.raises(ValueError):
        LMServingEngine(model, slots=2, cache_len=48, block_len=4,
                        kv_quant="int8", decode_attn="paged_kernel")
    # disaggregated serving keeps full-precision pools
    with pytest.raises(ValueError):
        LMServingEngine(model, slots=2, cache_len=48, block_len=4,
                        kv_quant="int8",
                        migrate=lambda *a, **k: None)
