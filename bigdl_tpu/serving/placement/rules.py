"""Sharding rules for SERVED nn modules, and a chunked sharded loader.

`parallel.tensor_parallel` wrote its specs for (in, out) training
weights; `nn.Linear` preserves the Torch layout — weight is
(output_size, input_size) with y = x @ W.T — so the Megatron dims flip:
column-parallel (shard the OUTPUT dim, no forward comm) is P(axis, None)
here and row-parallel (shard the INPUT dim, one psum) is P(None, axis).

`serving_tp_rules` derives the alternating col/row pairing from the
module tree itself (forward-order Linears inside Containers), which
also makes it layout-uniform over int8 `QTensor` leaves: a QTensor's
children are (q, scale) with q shaped like the weight and scale
(out, 1) keepdims, so the same divisibility-guarded shape rule shards
q and scale together under col and correctly replicates the (out, 1)
scale under row.  Any dim the TP degree does not divide degrades to
replicated — sharding specs are placement hints, XLA guarantees the
same numerics either way.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.parallel.mesh import MODEL_AXIS, replicated


def _linear_prefixes(module, prefix: str = "") -> list:
    """Param-path prefixes of every Linear, in forward order."""
    from bigdl_tpu.nn.linear import Linear
    if isinstance(module, Linear):
        return [prefix]
    out = []
    mods = getattr(module, "modules", None)
    if isinstance(mods, (list, tuple)):
        for i, m in enumerate(mods):
            out.extend(_linear_prefixes(m, f"{prefix}['{i}']"))
    return out


def serving_tp_rules(module, mesh: Mesh, axis: str = MODEL_AXIS
                     ) -> Callable[[tuple, Any], Optional[NamedSharding]]:
    """Megatron col/row alternation over a served module's Linears.

    Returns a ``rules(path, leaf)`` callable for
    :func:`shard_params_chunked` / ``tensor_parallel.shard_params``.
    Leaves outside any Linear, and dims ``tp`` does not divide, return
    None (caller replicates).  TransformerLM serving does NOT go
    through this — it has its own layer-stacked
    ``transformer_lm_tp_rules``.
    """
    tp = mesh.shape[axis]
    prefixes = _linear_prefixes(module)

    def _col(shp) -> Optional[NamedSharding]:
        # shard dim 0: weight (out, in), bias (out,), qscale (out, 1)
        if len(shp) >= 1 and shp[0] >= tp and shp[0] % tp == 0:
            return NamedSharding(mesh, P(axis, *([None] * (len(shp) - 1))))
        return None

    def _row(shp) -> Optional[NamedSharding]:
        # shard dim 1: weight (out, in); bias and (out, 1) qscale stay
        # replicated — the psum output is full-width on every device
        if len(shp) >= 2 and shp[1] >= tp and shp[1] % tp == 0:
            return NamedSharding(mesh, P(None, axis, *([None] * (len(shp) - 2))))
        return None

    def rules(path, leaf):
        if tp <= 1:
            return None
        name = jax.tree_util.keystr(path)
        shp = tuple(getattr(leaf, "shape", ()))
        for j, pfx in enumerate(prefixes):
            if name.startswith(pfx + "["):
                return _col(shp) if j % 2 == 0 else _row(shp)
        return None

    return rules


def shard_params_chunked(params: Any,
                         rules: Callable[[tuple, Any], Optional[NamedSharding]],
                         mesh: Mesh, *, chunk_bytes: Optional[int] = None) -> Any:
    """`tensor_parallel.shard_params`, but every leaf rides the
    resilient 32 MB-chunked transfer straight to its sharded layout —
    one pass, no dense single-device detour (a replicate-then-reshard
    would hold the whole tree on one device first).
    """
    from bigdl_tpu.utils.transfer import DEFAULT_CHUNK_BYTES, chunked_device_put
    if chunk_bytes is None:
        chunk_bytes = DEFAULT_CHUNK_BYTES
    rep = replicated(mesh)

    def place(path, leaf):
        return chunked_device_put(leaf, chunk_bytes=chunk_bytes,
                                  device=rules(path, leaf) or rep)

    return jax.tree_util.tree_map_with_path(place, params)
