"""Pallas TPU kernels for the hot ops.

The reference backs its hot loops with a native library (SURVEY.md §2.1);
on TPU XLA fusion covers most of that role, and this package holds the
kernels where explicit control over VMEM/MXU tiling beats XLA's default
schedule.  Every op has a pure-XLA fallback; kernels run in interpreter
mode off-TPU so the test suite exercises them on CPU.
"""
from bigdl_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention, flash_attention_with_lse,
)
from bigdl_tpu.ops.grouped_attention import (  # noqa: F401
    grouped_decode_attention,
)
from bigdl_tpu.ops.kda_step import (  # noqa: F401
    kda_step_path, kda_step_rows,
)
from bigdl_tpu.ops.latent_attention import (  # noqa: F401
    latent_decode_attention,
)
