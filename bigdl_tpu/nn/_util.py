"""Shared helpers for the nn layer zoo."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def to_axis(dim: int, ndim: int, n_input_dims: Optional[int] = None) -> int:
    """Convert a 1-based Torch/BigDL dimension to a 0-based axis.

    ``n_input_dims`` reproduces the reference's nInputDims convention: when
    the actual rank exceeds it, leading dims are batch dims and the 1-based
    ``dim`` counts from after them (e.g. JoinTable, SplitTable).
    Negative dims count from the end (Torch allows -1 = last).
    """
    if dim < 0:
        return ndim + dim
    axis = dim - 1
    if n_input_dims is not None and ndim > n_input_dims:
        axis += ndim - n_input_dims
    return axis


def fold_rng(rng, i: int):
    return None if rng is None else jax.random.fold_in(rng, i)


def cast_f32_leaves(tree, dtype):
    """The mixed-precision param cast (f32 leaves -> compute dtype,
    everything else untouched) — ONE definition shared by
    ``Optimizer.set_compute_dtype`` and the perf harnesses, so they
    measure exactly the recipe training uses."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, tree)


def match_compute_dtype(x, w):
    """AMP-style operand alignment for MXU-feeding ops: when the weight is
    a float of different precision than the float input, cast the input to
    the weight's dtype.  Mixed precision casts *params* to the compute
    dtype (optim.Optimizer.set_compute_dtype); aligning at the layer is
    what makes the matmul/conv actually run there — jnp's silent promotion
    would up-cast the bf16 weight back to f32, and lax.conv would reject
    the mismatch outright.  Inputs whose float payload is not resumable in
    low precision (1-based LookupTable/embedding ids riding float32) never
    reach this helper: id-consuming layers convert to int before any
    weight touches the value."""
    wdt = getattr(w, "dtype", None)  # QTensor weights align in-kernel
    if (wdt is not None and jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(wdt, jnp.floating)
            and x.dtype != wdt):
        return x.astype(wdt)
    return x


def same_pad(size: int, kernel: int, stride: int) -> tuple[int, int]:
    """SAME-style padding pair for one spatial dim."""
    out = -(-size // stride)
    total = max(0, (out - 1) * stride + kernel - size)
    return total // 2, total - total // 2


def one_based_index(idx: int, length: int) -> int:
    """1-based index with negative-from-end semantics (ref SelectTable)."""
    return idx - 1 if idx > 0 else length + idx
