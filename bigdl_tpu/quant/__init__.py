"""bigdl_tpu.quant — int8/bf16 quantization: storage AND compute.

The inference-precision subsystem (ref: BigDL's int8 model quantization,
arXiv 1804.05839; BigDL 2.0 Nano's inference optimizations, arXiv
2204.01715).  Symmetric int8 weights with per-channel f32 scales
(:class:`QTensor`) in two regimes, selected by
``QuantPolicy(compute=...)``:

- **storage-only ("dequant")**: activations stay in the compute dtype;
  the MXU contraction runs bf16 operands with f32 accumulation (the
  ops/flash_attention.py recipe) after an in-kernel dequant.
- **true int8 compute ("int8")**: activations are quantized per
  token (:mod:`~bigdl_tpu.quant.activations`, dynamic or calibrated)
  and BOTH int8 operands feed the MXU with exact int32 accumulation,
  then one f32 rescale.  fp8 variants gate on capable device kinds.

Entry points:

- ``model.quantize("int8", compute="int8")`` — quantized clone (nn.Module)
- :func:`quantize_params`          — the pytree-level transform + policy
- ``SpecConfig(drafter_compute="int8")`` — the int8-compute drafter
"""
from bigdl_tpu.quant.qtensor import (QMAX, QTensor, dequantize_array,
                                     is_qtensor, quantize_array)
from bigdl_tpu.quant.kernels import (qconv, qconv_i8, qlinear, qlinear_i8,
                                     qmatmul, qmatmul_i8)
from bigdl_tpu.quant.activations import (ActCalibrator, attach_act_scales,
                                         fp8_supported, quantize_per_token)
from bigdl_tpu.quant.transform import (QuantPolicy, dequantize_entry,
                                       dequantize_params,
                                       params_compute_tag, params_dtype_tag,
                                       params_nbytes, quantize_params,
                                       set_compute_mode,
                                       stage_quantized_params)

__all__ = [
    "ActCalibrator", "QMAX", "QTensor", "QuantPolicy", "attach_act_scales",
    "dequantize_array", "dequantize_entry", "dequantize_params",
    "fp8_supported", "is_qtensor", "params_compute_tag", "params_dtype_tag",
    "params_nbytes", "qconv", "qconv_i8", "qlinear", "qlinear_i8",
    "qmatmul", "qmatmul_i8", "quantize_array", "quantize_params",
    "quantize_per_token", "set_compute_mode",
    "stage_quantized_params",
]
