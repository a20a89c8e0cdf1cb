"""Pytree-level quantization transform with an include/exclude policy.

``quantize_params`` walks a params pytree and replaces eligible weight
leaves with :class:`~bigdl_tpu.quant.qtensor.QTensor` (int8 mode) or
bf16 arrays (bf16 mode).  What is *eligible* is the policy's job, and
the defaults encode the same precision rule the training stack already
follows (optim.Optimizer.set_compute_dtype + nn/_util.cast_f32_leaves):

- norms and biases stay f32 — they are tiny (1-D, or the ``b*`` leaf
  names of the vmap-stacked transformer blocks) and their values gate
  every channel, so there are no bytes to win and real accuracy to lose;
- embedding tables stay f32 — their rows are *gathered*, not matmul'd
  (no MXU contraction to hide the dequant in), and the id path that
  feeds them rides float-encoded 1-based indices above bf16's exact-
  integer range (the optimizer.py rule for why inputs are never cast);
- everything 2-D+ and big enough to matter is quantized.

When the owning ``module`` is supplied (Module.quantize does), the
walker resolves each leaf's owner the way utils/torch_import.py walks
containers, so Linear/SpatialConvolution weights get their *native*
per-out-channel scale layout and dequantize inside their own MXU kernel
(quant/kernels.py); every other module's leaves are marked non-native
and are expanded back at the jit entry seam (:func:`dequantize_entry`)
— inside the traced function, so serving still stores and uploads int8.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.quant.qtensor import (QTensor, dequantize_array, is_qtensor,
                                     quantize_array)
from bigdl_tpu.utils.transfer import DEFAULT_CHUNK_BYTES, chunked_device_put

#: leaf names that are never quantized: biases in every naming scheme
#: the zoo uses (``bias``, transformer-block ``b1``/``bq``/... riding a
#: vmap layer axis), norm affine leaves, and embedding/positional tables
_SKIP_NAME_RE = re.compile(r"^(bias|b\d*|b[qkvo]|beta|gamma|embed(ding)?"
                           r"|pos(_emb)?|wte|wpe)$")

#: non-native leaf names whose consuming matmul site is QTensor-aware
#: (quant/kernels.qmatmul: transformer attention projections, MLP
#: halves, the untied head).  Only these may carry a compute mode past
#: the jit-entry dequant seam — any other generic leaf is consumed by
#: code that reads params directly and must keep expanding there.
_COMPUTE_NAME_RE = re.compile(r"^(wq|wk|wv|wo|w1|w2|head)$")


class QuantPolicy:
    """Include/exclude policy for :func:`quantize_params`.

    Args:
        dtype: ``"int8"`` (QTensor storage) or ``"bf16"`` (plain cast).
        min_ndim: leaves below this rank are skipped (1-D = norm
            weights/biases — never worth quantizing).
        min_size: leaves with fewer elements are skipped (the scale
            overhead and accuracy risk buy back almost no bytes).
        skip_name_re: regex on the leaf's own key name.
        skip_path_re: optional regex on the full ``/``-joined tree path.
        compute: what the consuming kernel does with int8 leaves —
            ``"dequant"`` (storage-only, the default), ``"int8"`` (true
            int8×int8 MXU compute with per-token activation
            quantization), or ``"fp8"`` (gated on capable device kinds
            via activations.fp8_supported()).
        compute_name_re: which NON-native leaf names are allowed to
            carry a non-dequant compute mode (defaults to the
            transformer matmul sites kernels.qmatmul serves); native
            Linear/Conv weights always qualify — their own layer
            kernels dispatch.
    """

    def __init__(self, dtype: str = "int8", *, min_ndim: int = 2,
                 min_size: int = 128,
                 skip_name_re=_SKIP_NAME_RE,
                 skip_path_re=None,
                 compute: str = "dequant",
                 compute_name_re=_COMPUTE_NAME_RE):
        if dtype not in ("int8", "bf16"):
            raise ValueError(f"unsupported quant dtype {dtype!r} "
                             "(int8 or bf16)")
        if compute not in ("dequant", "int8", "fp8"):
            raise ValueError(f"unsupported compute mode {compute!r} "
                             "(dequant, int8 or fp8)")
        if compute != "dequant" and dtype != "int8":
            raise ValueError(f"compute={compute!r} needs dtype='int8' "
                             f"(got {dtype!r}): only int8 storage feeds "
                             "the low-precision matmul paths")
        if compute == "fp8":
            from bigdl_tpu.quant.activations import fp8_supported
            if not fp8_supported():
                raise NotImplementedError(
                    "compute='fp8' is gated on fp8-capable device "
                    "kinds; this backend is not one (int8 and dequant "
                    "work everywhere)")
        self.dtype = dtype
        self.compute = compute
        self.min_ndim = int(min_ndim)
        self.min_size = int(min_size)
        self.skip_name_re = (re.compile(skip_name_re)
                             if isinstance(skip_name_re, str) else skip_name_re)
        self.skip_path_re = (re.compile(skip_path_re)
                             if isinstance(skip_path_re, str) else skip_path_re)
        self.compute_name_re = (re.compile(compute_name_re)
                                if isinstance(compute_name_re, str)
                                else compute_name_re)

    def wants(self, path: Tuple[str, ...], leaf) -> bool:
        """Should this leaf be quantized?  Only float leaves qualify —
        int buffers/ids pass through untouched."""
        name = path[-1] if path else ""
        if self.skip_name_re is not None and self.skip_name_re.match(name):
            return False
        if self.skip_path_re is not None \
                and self.skip_path_re.search("/".join(path)):
            return False
        if getattr(leaf, "ndim", 0) < self.min_ndim:
            return False
        if getattr(leaf, "size", 0) < self.min_size:
            return False
        dt = getattr(leaf, "dtype", None)
        return dt is not None and jnp.issubdtype(dt, jnp.floating)


# ---------------------------------------------------------------------- #
# module-aware owner resolution                                          #
# ---------------------------------------------------------------------- #
def _module_index(module) -> Dict[Tuple[str, ...], Any]:
    """(tree-path) -> owning leaf module, walking containers the way
    utils/torch_import does (index keys for containers, named keys for
    the wrapper modules)."""
    from bigdl_tpu.utils.torch_import import _child_keys

    index: Dict[Tuple[str, ...], Any] = {}

    def walk(mod, path: Tuple[str, ...]):
        children = getattr(mod, "modules", None)
        if children:
            for key, child in zip(_child_keys(mod), children):
                walk(child, path + (key,))
            return
        index[path] = mod

    walk(module, ())
    return index


def _owner_of(index: Dict[Tuple[str, ...], Any],
              leaf_path: Tuple[str, ...]):
    """Longest registered prefix of ``leaf_path`` (nested leaf params
    like Scale's {cmul,cadd} still belong to the Scale module)."""
    for n in range(len(leaf_path) - 1, -1, -1):
        mod = index.get(leaf_path[:n])
        if mod is not None:
            return mod
    return None


def _native_spec(owner, name: str):
    """(reduce_axes, native) when the owner dequantizes this leaf inside
    its own kernel; None -> generic handling.  Embedding owners return
    the sentinel "skip"."""
    if owner is None:
        return None
    from bigdl_tpu import nn
    if isinstance(owner, nn.LookupTable):
        return "skip"
    if name != "weight":
        return None
    if isinstance(owner, nn.SpatialConvolution):
        # OIHW, grouped included: contraction over (I/g, kH, kW); the
        # transposed/map variants are separate classes -> generic
        return (1, 2, 3), True
    if isinstance(owner, nn.Linear):
        return (-1,), True  # (out, in): contraction over in
    return None


# ---------------------------------------------------------------------- #
# the transform                                                          #
# ---------------------------------------------------------------------- #
def quantize_params(params, dtype: str = "int8", *,
                    policy: Optional[QuantPolicy] = None,
                    module=None, report: Optional[dict] = None):
    """Quantize eligible leaves of ``params``; returns a new tree.

    ``module`` (optional) enables owner-aware decisions: native scale
    layouts for Linear/Conv and automatic embedding exclusion.
    ``report`` (optional dict) is filled with byte counts and per-layer
    max abs dequantization error — the numbers the obs gauges publish.
    """
    policy = policy or QuantPolicy(dtype)
    if policy.dtype != dtype:
        policy = QuantPolicy(dtype, min_ndim=policy.min_ndim,
                             min_size=policy.min_size,
                             skip_name_re=policy.skip_name_re,
                             skip_path_re=policy.skip_path_re,
                             compute=policy.compute,
                             compute_name_re=policy.compute_name_re)
    index = _module_index(module) if module is not None else {}
    per_layer_err: Dict[str, float] = {}
    per_layer_risk: Dict[str, float] = {}
    stats = {"bytes_orig": 0, "bytes_quant": 0,
             "quantized_leaves": 0, "skipped_leaves": 0}

    def leaf_bytes(a) -> int:
        return int(a.size) * jnp.dtype(a.dtype).itemsize

    def transform(node, path: Tuple[str, ...]):
        if isinstance(node, dict):
            return {k: transform(v, path + (str(k),))
                    for k, v in node.items()}
        if is_qtensor(node):  # already quantized: idempotent pass
            stats["bytes_orig"] += (int(node.size)
                                    * jnp.dtype(node.orig_dtype).itemsize)
            stats["bytes_quant"] += node.nbytes
            stats["quantized_leaves"] += 1
            return node
        if not hasattr(node, "dtype"):
            return node
        stats["bytes_orig"] += leaf_bytes(node)
        spec = _native_spec(_owner_of(index, path), path[-1] if path else "")
        if spec == "skip" or not policy.wants(path, node):
            stats["bytes_quant"] += leaf_bytes(node)
            stats["skipped_leaves"] += 1
            return node
        stats["quantized_leaves"] += 1
        if dtype == "bf16":
            out = node.astype(jnp.bfloat16)
            stats["bytes_quant"] += leaf_bytes(out)
            if report is not None:
                err = float(jnp.max(jnp.abs(
                    node - out.astype(node.dtype))))
                per_layer_err["/".join(path)] = err
            return out
        if spec is not None:
            reduce_axes, native = spec
        else:
            # generic x @ w layout (transformer blocks, head
            # projections, vmap-stacked weights): contraction is the
            # second-to-last axis; every other axis keeps its own scale
            reduce_axes, native = (-2,), False
        name = path[-1] if path else ""
        compute = policy.compute
        if compute != "dequant" and not native \
                and not (policy.compute_name_re is not None
                         and policy.compute_name_re.match(name)):
            # generic leaf with no QTensor-aware consumer: storage-only
            compute = "dequant"
        qt = quantize_array(node, reduce_axes, native=native,
                            compute=compute)
        stats["bytes_quant"] += qt.nbytes
        if report is not None:
            err = float(jnp.max(jnp.abs(node - qt.dequantize(node.dtype))))
            per_layer_err["/".join(path)] = err
            if compute == "int8":
                per_layer_risk["/".join(path)] = _overflow_risk(
                    qt, reduce_axes)
        return qt

    out = transform(params, ())
    if report is not None:
        report.update(stats)
        report["dtype"] = dtype
        report["compute_mode"] = policy.compute
        report["payload_ratio"] = (stats["bytes_quant"]
                                   / max(stats["bytes_orig"], 1))
        report["bytes_saved"] = stats["bytes_orig"] - stats["bytes_quant"]
        report["per_layer_max_abs_err"] = per_layer_err
        report["max_abs_dequant_error"] = (max(per_layer_err.values())
                                           if per_layer_err else 0.0)
        report["per_layer_overflow_risk"] = per_layer_risk
        report["overflow_risk"] = (max(per_layer_risk.values())
                                   if per_layer_risk else 0.0)
    return out


def _overflow_risk(qt: QTensor, reduce_axes) -> float:
    """Worst-case int32-accumulator fill for an int8-compute matmul:
    ``max|q_w| * 127 * K / 2^31`` with K the contraction length — 127 is
    the activation bound by construction (per-token symmetric quant).
    A value near 1.0 means a bad calibration or a pathological weight
    could wrap the accumulator and silently corrupt acceptance rate;
    the obs gauge surfaces it before that happens."""
    shape = qt.q.shape
    axes = tuple(reduce_axes) if reduce_axes is not None \
        else tuple(range(len(shape)))
    k = 1
    for a in axes:
        k *= int(shape[a])
    qmax_w = int(jnp.max(jnp.abs(qt.q.astype(jnp.int32))))
    return float(qmax_w) * 127.0 * float(k) / float(2 ** 31)


def dequantize_params(params, dtype=None):
    """Expand every QTensor back to a dense array (``dtype`` overrides
    each leaf's pre-quantization dtype).  bf16-cast leaves are NOT
    widened — the cast already lost the bits."""
    return jax.tree_util.tree_map(
        lambda n: dequantize_array(n, dtype) if is_qtensor(n) else n,
        params, is_leaf=is_qtensor)


def dequantize_entry(params):
    """The jit-entry seam: expand non-native *dequant-mode* QTensors
    (whose consuming module reads params directly) and pass everything
    else through — native leaves dequantize (or int8-compute) inside
    their own layer kernels, and non-dequant compute leaves are
    consumed by the QTensor-aware matmul sites (kernels.qmatmul), so
    they must survive the seam as int8.  Traced inside jit, so the
    expansion fuses and int8 remains the stored/transferred form."""
    return jax.tree_util.tree_map(
        lambda n: (n.dequantize()
                   if is_qtensor(n) and not n.native
                   and n.compute == "dequant" else n),
        params, is_leaf=is_qtensor)


def set_compute_mode(params, compute: str, *,
                     compute_name_re=_COMPUTE_NAME_RE):
    """Rewrite the compute mode of an already-quantized tree (aux-only:
    int8 payloads are shared, nothing re-rounds).  The same
    consumable-name guard as quantize_params applies to non-native
    leaves — a generic leaf whose consumer reads params directly keeps
    expanding at the seam regardless of the requested mode.  This is
    how an int8-storage *target* becomes its own int8-*compute* drafter
    without a second copy of the weights."""
    if compute not in ("dequant", "int8", "fp8"):
        raise ValueError(f"compute must be 'dequant', 'int8' or 'fp8', "
                         f"got {compute!r}")
    name_re = (re.compile(compute_name_re)
               if isinstance(compute_name_re, str) else compute_name_re)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (str(k),)) for k, v in node.items()}
        if is_qtensor(node):
            name = path[-1] if path else ""
            eff = compute
            if compute != "dequant" and not node.native \
                    and not (name_re is not None and name_re.match(name)):
                eff = "dequant"
            if eff != node.compute:
                return node.with_compute(eff)
        return node

    return walk(params, ())


def params_compute_tag(params) -> Optional[str]:
    """The dominant compute mode of a params tree ("fp8" > "int8" >
    "dequant"; None when nothing is quantized) — surfaced by
    quant_report, DraftModel.describe() and the serving/lm/spec/*
    gauges so a storage-only drafter is never mistaken for a true
    int8-compute one."""
    best = None
    rank = {"dequant": 0, "int8": 1, "fp8": 2}
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_qtensor):
        if is_qtensor(leaf):
            if best is None or rank[leaf.compute] > rank[best]:
                best = leaf.compute
    return best


# ---------------------------------------------------------------------- #
# serving integration helpers                                            #
# ---------------------------------------------------------------------- #
def params_dtype_tag(params) -> str:
    """The quant dtype a params tree serves at — part of the serving
    CompileCache bucket key, so f32 and int8 replicas of one model hold
    separate executables in the same cache."""
    tag = "f32"
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_qtensor):
        if is_qtensor(leaf):
            return "int8"
        if getattr(leaf, "dtype", None) == jnp.bfloat16:
            tag = "bf16"
    return tag


def stage_quantized_params(params, *,
                           chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                           device=None):
    """Re-stage QTensor payloads host->device through the shared 32 MB
    chunked-transfer discipline (utils/transfer.py) and count the bytes
    that moved:
    the int8 payload is ~4x fewer wire bytes than the f32 it replaces.

    Returns ``(params, bytes_moved)``; non-quantized leaves are left
    where they already live.
    """
    moved = 0

    def stage(node):
        nonlocal moved
        if not is_qtensor(node):
            return node
        q = chunked_device_put(np.asarray(node.q), "int8",
                               chunk_bytes=chunk_bytes, device=device)
        scale = chunked_device_put(np.asarray(node.scale),
                                   chunk_bytes=chunk_bytes, device=device)
        moved += node.nbytes
        return QTensor(q, scale, node.orig_dtype, node.native,
                       node.compute, node.act_scale)

    out = jax.tree_util.tree_map(stage, params, is_leaf=is_qtensor)
    return out, moved


def params_nbytes(params) -> int:
    """Total stored bytes of a params tree (QTensor-aware)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_qtensor):
        if is_qtensor(leaf):
            total += leaf.nbytes
        elif hasattr(leaf, "dtype"):
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total
