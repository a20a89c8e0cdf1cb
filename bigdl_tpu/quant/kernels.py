"""Quantized-weight kernels: dequant-on-the-fly AND true int8 compute.

Two MXU recipes live here:

- **dequant** (the storage-only default, mirrors ops/flash_attention.py):
  operands in bf16 (full MXU rate on TPU), accumulation in f32 via
  ``preferred_element_type`` — never bf16 accumulation, never f32
  operands.  The int8 weight is expanded ``q * scale`` in f32 and
  rounded once to bf16 right at the operand seam; XLA fuses the expand
  into the producing loop, so no f32 copy of the weight ever
  materializes in HBM.

- **int8 compute** (``*_i8``): the activation is quantized per token
  (quant/activations.py) and BOTH int8 operands feed the MXU directly
  through ``lax.dot_general(..., preferred_element_type=jnp.int32)`` —
  exact int32 accumulation, then ONE f32 rescale by (per-token
  activation scale) × (per-channel weight scale).  On int8-native MXUs
  this doubles matmul rate over bf16; the f32 result is bit-identical
  to the mathematically equivalent f32 computation of the quantized
  operands, so the error budget is exactly the two quantization
  roundings and nothing else.

``qlinear``/``qconv``/``qmatmul`` are the dispatch seam: a QTensor's
``compute`` aux picks the recipe.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from bigdl_tpu.quant.qtensor import QTensor, is_qtensor


def _operand(x):
    """bf16 MXU operand for a float activation; integer inputs (none of
    the native layers take them) pass through untouched."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.bfloat16)
    return x


# ---------------------------------------------------------------------- #
# dequant-on-the-fly (storage-only) recipe                               #
# ---------------------------------------------------------------------- #
def qlinear(x, qweight: QTensor, bias=None):
    """Quantized ``y = x @ W.T + b`` (nn.Linear semantics, weight
    ``(out, in)`` with per-out-channel scales ``(out, 1)``); compute
    mode dispatched per the weight's ``compute`` aux."""
    if qweight.compute == "int8":
        return qlinear_i8(x, qweight, bias)
    w = qweight.dequantize(jnp.bfloat16)
    y = jnp.matmul(_operand(x), w.T,
                   preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(jnp.dtype(qweight.orig_dtype))


def qconv(x, qweight: QTensor, *, window_strides, padding,
          dimension_numbers, feature_group_count: int = 1,
          rhs_dilation=None):
    """Quantized ``lax.conv_general_dilated`` (OIHW weight with
    per-out-plane scales ``(O, 1, 1, 1)``); compute mode dispatched per
    the weight's ``compute`` aux."""
    kw = dict(window_strides=window_strides, padding=padding,
              dimension_numbers=dimension_numbers,
              feature_group_count=feature_group_count,
              rhs_dilation=rhs_dilation)
    if qweight.compute == "int8":
        return qconv_i8(x, qweight, **kw)
    w = qweight.dequantize(jnp.bfloat16)
    y = lax.conv_general_dilated(
        _operand(x), w, preferred_element_type=jnp.float32, **kw)
    return y.astype(jnp.dtype(qweight.orig_dtype))


# ---------------------------------------------------------------------- #
# true int8×int8 compute                                                 #
# ---------------------------------------------------------------------- #
def qlinear_i8(x, qweight: QTensor, bias=None):
    """``y = x @ W.T + b`` with int8×int8 MXU compute: per-token
    activation quantization, int32 accumulation, one f32 rescale by
    act_scale (..., 1) × weight scale (out,)."""
    from bigdl_tpu.quant.activations import quantize_per_token
    x = jnp.asarray(x)
    xq, xs = quantize_per_token(x, scale=qweight.act_scale)
    acc = lax.dot_general(
        xq, qweight.q,
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)            # (..., out) exact
    ws = qweight.scale.reshape(-1)                   # (out,)
    y = acc.astype(jnp.float32) * xs * ws
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(jnp.dtype(qweight.orig_dtype))


def qmatmul(x, w):
    """Generic ``x @ w`` for the ``(in, out)``-layout weights the
    transformer consumes directly (attention projections, MLP halves,
    untied head) — QTensor-aware, plain arrays fall straight through.
    This is the one seam the int8-compute drafter rides: every matmul
    site routes here, and the weight's ``compute`` aux decides the
    recipe per leaf."""
    if not is_qtensor(w):
        return x @ w
    x = jnp.asarray(x)
    if w.q.ndim == 2:
        if w.compute == "int8":
            return qmatmul_i8(x, w)
        if w.compute == "fp8":
            return qmatmul_f8(x, w)
    # dequant fallback reproduces the jit-entry-seam numerics exactly:
    # expand to orig dtype, matmul at the activation's precision
    wd = w.dequantize()
    if (jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != wd.dtype):
        x = x.astype(wd.dtype)
    return x @ wd


def qmatmul_i8(x, qweight: QTensor):
    """``x @ w`` (generic layout ``(in, out)``, scales ``(1, out)``)
    with int8×int8 MXU compute — the stacked-transformer-weight twin of
    :func:`qlinear_i8` (lax.scan slices a (L, in, out) QTensor into
    per-layer (in, out) children; the aux rides along)."""
    from bigdl_tpu.quant.activations import quantize_per_token
    x = jnp.asarray(x)
    xq, xs = quantize_per_token(x, scale=qweight.act_scale)
    acc = lax.dot_general(
        xq, qweight.q,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)            # (..., out) exact
    ws = qweight.scale.reshape(-1)                   # (out,)
    y = acc.astype(jnp.float32) * xs * ws
    return y.astype(jnp.dtype(qweight.orig_dtype))


def qconv_i8(x, qweight: QTensor, *, window_strides, padding,
             dimension_numbers, feature_group_count: int = 1,
             rhs_dilation=None):
    """int8×int8 convolution: per-SAMPLE activation quantization (one
    scale over every non-batch axis — conv has no per-output-pixel
    pre-quantization), int32 accumulation, f32 rescale placed along the
    layout's batch/feature dims resolved from ``dimension_numbers``."""
    from bigdl_tpu.quant.activations import quantize_per_token
    x = jnp.asarray(x)
    xf = x.astype(jnp.float32)
    dn = lax.conv_dimension_numbers(x.shape, qweight.q.shape,
                                    dimension_numbers)
    bdim = dn.lhs_spec[0]
    red = tuple(a for a in range(x.ndim) if a != bdim)
    if qweight.act_scale is not None:
        s = jnp.full((x.shape[bdim],), jnp.float32(qweight.act_scale))
        s = s.reshape([-1 if a == bdim else 1 for a in range(x.ndim)])
        xq = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    else:
        amax = jnp.max(jnp.abs(xf), axis=red, keepdims=True)
        s = jnp.maximum(amax, 1e-12) / 127.0
        xq = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    acc = lax.conv_general_dilated(
        xq, qweight.q,
        window_strides=window_strides, padding=padding,
        dimension_numbers=dimension_numbers,
        feature_group_count=feature_group_count,
        rhs_dilation=rhs_dilation,
        preferred_element_type=jnp.int32)
    ob, of = dn.out_spec[0], dn.out_spec[1]
    out_ndim = acc.ndim
    ws = qweight.scale.reshape(-1)                   # (O,)
    ws = ws.reshape([-1 if a == of else 1 for a in range(out_ndim)])
    sb = s.reshape(-1).reshape(
        [-1 if a == ob else 1 for a in range(out_ndim)])
    y = acc.astype(jnp.float32) * sb * ws
    return y.astype(jnp.dtype(qweight.orig_dtype))


def qmatmul_f8(x, qweight: QTensor):
    """fp8(e4m3) variant of :func:`qmatmul_i8`: both operands cast to
    fp8 with per-token / per-channel scaling, f32 accumulation.  Only
    reachable behind activations.fp8_supported() (policy gate) — kept
    beside the int8 path so capable device kinds get the same dispatch
    seam when the fp8 duel lands."""
    from bigdl_tpu.quant.activations import (FP8_DTYPE,
                                             quantize_per_token_fp8)
    x = jnp.asarray(x)
    xq, xs = quantize_per_token_fp8(x, force=True)
    wf = qweight.q.astype(jnp.float32)               # re-express int8 in fp8
    wmax = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True), 1e-12)
    wq = (wf / (wmax / 448.0)).astype(FP8_DTYPE)
    acc = lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    ws = (qweight.scale.reshape(-1) * (wmax.reshape(-1) / 448.0))
    y = acc * xs * ws
    return y.astype(jnp.dtype(qweight.orig_dtype))
