"""Import GPT-2-family PyTorch checkpoints into ``TransformerLM``.

The transformer-family analog of the CNN import path
(utils/torch_import.py; ref example/loadmodel/ModelValidator.scala's
role): the dominant pretrained-LM checkpoint layout is Hugging Face
GPT-2's, whose module conventions TransformerLM already matches
architecturally — pre-LN blocks, fused qkv projection, tanh-GELU MLP,
learned positions, tied embeddings, final LayerNorm.  HF's ``Conv1D``
stores weights as ``(in, out)``, the same layout our projection and
MLP matrices use, so the copy is split/stack-only:

    HF key                              TransformerLM params
    ------------------------------      -------------------------------
    wte.weight (V, H)                   embed
    wpe.weight (T, H)                   pos           (learned only)
    h.<i>.ln_1.{weight,bias}            blocks.ln1    (stacked over i)
    h.<i>.attn.c_attn.{weight,bias}     blocks.attn.{wq,wk,wv,bq,bk,bv}
                                        (fused (H, 3H) split q|k|v)
    h.<i>.attn.c_proj.{weight,bias}     blocks.attn.{wo,bo}
    h.<i>.mlp.c_fc.{weight,bias}        blocks.{w1,b1}
    h.<i>.mlp.c_proj.{weight,bias}      blocks.{w2,b2}
    h.<i>.ln_2.{weight,bias}            blocks.ln2
    ln_f.{weight,bias}                  ln_f
    lm_head.weight (V, H)               head = weight.T  (untied only)

Per-layer tensors stack onto the leading layer axis — the exact layout
``lax.scan`` consumes (TransformerLM.init builds the same way).  A
``transformer.`` prefix (GPT2LMHeadModel) is stripped automatically.

Oracled whole-model against the live Hugging Face implementation in
``tests/test_transformer_gpt2_oracle.py``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np
import jax.numpy as jnp

from bigdl_tpu.utils.torch_import import _to_numpy, device_array


def load_gpt2_state_dict(model, state_dict) -> "TransformerLM":
    """Copy a GPT-2 checkpoint (``GPT2Model``/``GPT2LMHeadModel`` state
    dict, tensors or arrays) into a built ``TransformerLM``.  The model
    configuration must match the checkpoint (vocab/hidden/layers/heads,
    ``pos_encoding="learned"``); mismatches raise with both shapes."""
    sd: Dict[str, np.ndarray] = {}
    for k, v in state_dict.items():
        if k.startswith("transformer."):
            k = k[len("transformer."):]
        sd[k] = _to_numpy(v)

    if model.moe_experts:
        raise ValueError("GPT-2 checkpoints carry dense MLP blocks — a "
                         "moe_experts>0 TransformerLM cannot load them")
    params = model._built()
    params = {k: v for k, v in params.items()}  # shallow copy of top level
    h = model.hidden_size
    L = model.n_layers

    def take(key, expect_shape):
        if key not in sd:
            raise ValueError(f"checkpoint has no '{key}' "
                             f"(keys: {sorted(sd)[:8]}...)")
        a = sd[key]
        if tuple(a.shape) != tuple(expect_shape):
            raise ValueError(f"{key}: checkpoint shape {tuple(a.shape)} vs "
                             f"model {tuple(expect_shape)}")
        return a.astype(np.float32)

    params["embed"] = device_array(
        take("wte.weight", (model.vocab_size, h)))
    if model.pos_encoding != "learned":
        raise ValueError("GPT-2 checkpoints carry learned positions — "
                         "build the TransformerLM with "
                         "pos_encoding='learned'")
    if "wpe.weight" not in sd:
        raise ValueError(f"checkpoint has no 'wpe.weight' "
                         f"(keys: {sorted(sd)[:8]}...)")
    wpe = take("wpe.weight", (np.asarray(sd["wpe.weight"]).shape[0], h))
    if wpe.shape[0] < model.max_len:
        raise ValueError(f"checkpoint wpe covers {wpe.shape[0]} positions "
                         f"< model max_len {model.max_len}")
    params["pos"] = device_array(wpe[:model.max_len])

    blocks: Dict[str, list] = {}

    def put(path, value):
        blocks.setdefault(path, []).append(value)

    f = model.ffn_size
    for i in range(L):
        p = f"h.{i}."
        put(("ln1", "weight"), take(p + "ln_1.weight", (h,)))
        put(("ln1", "bias"), take(p + "ln_1.bias", (h,)))
        cw = take(p + "attn.c_attn.weight", (h, 3 * h))
        cb = take(p + "attn.c_attn.bias", (3 * h,))
        for j, (wn, bn) in enumerate((("wq", "bq"), ("wk", "bk"),
                                      ("wv", "bv"))):
            put(("attn", wn), cw[:, j * h:(j + 1) * h])
            put(("attn", bn), cb[j * h:(j + 1) * h])
        put(("attn", "wo"), take(p + "attn.c_proj.weight", (h, h)))
        put(("attn", "bo"), take(p + "attn.c_proj.bias", (h,)))
        put(("ln2", "weight"), take(p + "ln_2.weight", (h,)))
        put(("ln2", "bias"), take(p + "ln_2.bias", (h,)))
        put(("w1",), take(p + "mlp.c_fc.weight", (h, f)))
        put(("b1",), take(p + "mlp.c_fc.bias", (f,)))
        put(("w2",), take(p + "mlp.c_proj.weight", (f, h)))
        put(("b2",), take(p + "mlp.c_proj.bias", (h,)))

    stacked: Dict = {}
    for path, per_layer in blocks.items():
        d = stacked
        for key in path[:-1]:
            d = d.setdefault(key, {})
        d[path[-1]] = device_array(np.stack(per_layer))
    params["blocks"] = stacked

    params["ln_f"] = {"weight": jnp.asarray(take("ln_f.weight", (h,))),
                      "bias": jnp.asarray(take("ln_f.bias", (h,)))}
    if not model.tie_embeddings:
        head = take("lm_head.weight", (model.vocab_size, h))
        params["head"] = device_array(np.ascontiguousarray(head.T))
    elif "lm_head.weight" in sd:
        # a fine-tuned checkpoint may have UNTIED its head; silently
        # substituting wte for a diverged lm_head would change the
        # output distribution with no error
        head = take("lm_head.weight", (model.vocab_size, h))
        if not np.allclose(head, np.asarray(params["embed"]),
                           rtol=1e-5, atol=1e-6):
            raise ValueError(
                "checkpoint's lm_head.weight differs from wte.weight "
                "(untied fine-tune) but the model was built with "
                "tie_embeddings=True — rebuild with "
                "tie_embeddings=False to import it faithfully")

    model.params = params
    return model


def export_gpt2_state_dict(model) -> Dict[str, np.ndarray]:
    """The reverse: a built ``TransformerLM``'s params as a GPT-2-layout
    state dict (numpy values, ``GPT2Model`` key convention — prepend
    ``transformer.`` and mirror ``lm_head.weight`` from ``wte.weight``
    for a ``GPT2LMHeadModel``).  Per-layer tensors unstack from the
    scan axis; q/k/v projections fuse back into ``c_attn``.  Round-trip
    and HF-load oracled in tests/test_transformer_gpt2_oracle.py."""
    if model.params is None:
        raise ValueError("model has no params to export — call "
                         "model.build(seed) (or train it) first")
    if model.moe_experts:
        raise ValueError("MoE blocks have no GPT-2 layout")
    if model.pos_encoding != "learned":
        raise ValueError("GPT-2's layout carries learned positions — "
                         "rope models cannot export to it")
    p = model.params
    out: Dict[str, np.ndarray] = {
        "wte.weight": np.asarray(p["embed"], np.float32),
        "wpe.weight": np.asarray(p["pos"], np.float32),
    }
    blocks = p["blocks"]

    def as32(x):
        return np.asarray(x, np.float32)

    for i in range(model.n_layers):
        pre = f"h.{i}."
        a = blocks["attn"]
        out[pre + "ln_1.weight"] = as32(blocks["ln1"]["weight"][i])
        out[pre + "ln_1.bias"] = as32(blocks["ln1"]["bias"][i])
        out[pre + "attn.c_attn.weight"] = np.concatenate(
            [as32(a["wq"][i]), as32(a["wk"][i]),
             as32(a["wv"][i])], axis=1)
        out[pre + "attn.c_attn.bias"] = np.concatenate(
            [as32(a["bq"][i]), as32(a["bk"][i]),
             as32(a["bv"][i])])
        out[pre + "attn.c_proj.weight"] = as32(a["wo"][i])
        out[pre + "attn.c_proj.bias"] = as32(a["bo"][i])
        out[pre + "ln_2.weight"] = as32(blocks["ln2"]["weight"][i])
        out[pre + "ln_2.bias"] = as32(blocks["ln2"]["bias"][i])
        out[pre + "mlp.c_fc.weight"] = as32(blocks["w1"][i])
        out[pre + "mlp.c_fc.bias"] = as32(blocks["b1"][i])
        out[pre + "mlp.c_proj.weight"] = as32(blocks["w2"][i])
        out[pre + "mlp.c_proj.bias"] = as32(blocks["b2"][i])
    out["ln_f.weight"] = np.asarray(p["ln_f"]["weight"], np.float32)
    out["ln_f.bias"] = np.asarray(p["ln_f"]["bias"], np.float32)
    if not model.tie_embeddings:
        out["lm_head.weight"] = np.asarray(p["head"], np.float32).T
    return out
