"""QTensor: symmetric weight-only int8 with per-channel f32 scales.

The storage half of the quantization subsystem (the reference line made
int8 inference a first-class feature — BigDL's model quantization,
arXiv 1804.05839 §5; carried through BigDL 2.0's Nano inference
optimizations, arXiv 2204.01715).  A ``QTensor`` packs a weight as

    q     int8, the original shape          (the 4x-smaller payload)
    scale f32, broadcast-shaped against q   (per-channel, keepdims)

with ``w ~= q * scale``.  Symmetric (no zero point): round-to-nearest
onto [-127, 127], scale = amax/127 over the *reduced* axes — the axes
that contract in the consuming matmul/conv, so each output channel (or
each (layer, out-channel) pair of a vmap-stacked transformer block)
carries its own scale and a single outlier channel cannot flatten the
resolution of every other one.

QTensor is a registered jax pytree node: it rides inside a params tree
through ``tree_map``, ``jit`` and AOT ``lower().compile()`` unchanged,
which is what lets the serving stack hold int8 and f32 replicas of the
same model side by side (see serving/compile_cache.py).

``native`` marks leaves whose owning layer dequantizes on the fly
inside its own kernel (Linear / SpatialConvolution feed the MXU bf16
operands with f32 accumulation — the ops/flash_attention.py dtype
recipe).  Non-native leaves are expanded back to ``orig_dtype`` at the
jit entry seam (transform.dequantize_entry), so *any* module in the zoo
serves from int8 storage even if its forward consumes params directly.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: smallest representable scale — an all-zero channel must not divide by 0
_EPS = 1e-12
#: symmetric int8 range; -128 is excluded so the range is sign-balanced
QMAX = 127


class QTensor:
    """int8 values + broadcast-shaped f32 scales (symmetric).

    ``compute`` selects what the consuming kernel does with the leaf:
    ``"dequant"`` (the storage-only default: expand to bf16/f32 before
    the MXU), ``"int8"`` (feed the int8 values straight to the MXU with
    int32 accumulation — quant/kernels.py ``*_i8`` paths) or ``"fp8"``.
    ``act_scale`` optionally pins a calibrated static
    per-tensor activation scale (quant/activations.py) — ``None`` means
    dynamic per-token quantization at trace time.  Both ride the pytree
    aux data, so tree_map/jit/AOT treat differently-configured leaves as
    distinct structures (separate compile-cache entries)."""

    __slots__ = ("q", "scale", "orig_dtype", "native", "compute",
                 "act_scale")

    def __init__(self, q, scale, orig_dtype: str = "float32",
                 native: bool = False, compute: str = "dequant",
                 act_scale: Optional[float] = None):
        if compute not in ("dequant", "int8", "fp8"):
            raise ValueError(f"compute must be 'dequant', 'int8' or "
                             f"'fp8', got {compute!r}")
        self.q = q
        self.scale = scale
        self.orig_dtype = str(orig_dtype)
        self.native = bool(native)
        self.compute = compute
        self.act_scale = None if act_scale is None else float(act_scale)

    # -- array-ish surface --------------------------------------------- #
    @property
    def shape(self) -> tuple:
        return tuple(self.q.shape)

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def size(self) -> int:
        return int(self.q.size)

    @property
    def nbytes(self) -> int:
        """Stored payload: int8 values plus the f32 scales."""
        return (int(self.q.size) * jnp.dtype(self.q.dtype).itemsize
                + int(self.scale.size) * jnp.dtype(self.scale.dtype).itemsize)

    def dequantize(self, dtype=None):
        """``q * scale`` in f32, cast to ``dtype`` (default: the dtype
        the weight had before quantization)."""
        target = jnp.dtype(dtype) if dtype is not None \
            else jnp.dtype(self.orig_dtype)
        w = self.q.astype(jnp.float32) * self.scale
        return w.astype(target)

    def with_compute(self, compute: str,
                     act_scale: Optional[float] = None) -> "QTensor":
        """Same payload, different compute mode (buffers are shared)."""
        return QTensor(self.q, self.scale, self.orig_dtype, self.native,
                       compute,
                       self.act_scale if act_scale is None else act_scale)

    def __repr__(self) -> str:
        return (f"QTensor(shape={self.shape}, scale={tuple(self.scale.shape)}, "
                f"orig={self.orig_dtype}, native={self.native}, "
                f"compute={self.compute})")


def _flatten(t: QTensor):
    return (t.q, t.scale), (t.orig_dtype, t.native, t.compute, t.act_scale)


def _unflatten(aux, children) -> QTensor:
    q, scale = children
    orig_dtype, native, compute, act_scale = aux
    return QTensor(q, scale, orig_dtype, native, compute, act_scale)


jax.tree_util.register_pytree_node(QTensor, _flatten, _unflatten)


def is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def quantize_array(w, reduce_axes: Optional[Tuple[int, ...]] = None,
                   *, native: bool = False,
                   compute: str = "dequant") -> QTensor:
    """Quantize ``w`` symmetrically to int8.

    ``reduce_axes`` are the axes the scale statistics reduce over — the
    contraction axes of the consuming op (Linear ``(out, in)``: (-1,);
    conv OIHW: (1, 2, 3); generic ``x @ w`` layouts: (-2,)).  ``None``
    reduces over everything = per-tensor (one scalar scale; kept for
    the accuracy comparison in tests — per-channel strictly dominates).
    """
    w = jnp.asarray(w)
    orig_dtype = str(w.dtype)
    wf = w.astype(jnp.float32)
    axes = tuple(reduce_axes) if reduce_axes is not None \
        else tuple(range(w.ndim))
    amax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / QMAX
    q = jnp.clip(jnp.round(wf / scale), -QMAX, QMAX).astype(jnp.int8)
    return QTensor(q, scale, orig_dtype, native, compute)


def dequantize_array(t, dtype=None):
    """Inverse of :func:`quantize_array`; passes plain arrays through."""
    if isinstance(t, QTensor):
        return t.dequantize(dtype)
    return t if dtype is None else jnp.asarray(t).astype(dtype)
