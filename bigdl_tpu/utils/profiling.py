"""Per-layer cost attribution from compiled XLA programs.

The reference accumulates per-module wall time in ``forward``/``backward``
(nn/abstractnn/AbstractModule.scala:125-135) plus conv ``im2colTime``
(nn/SpatialConvolution.scala:72-77).  Under ``jax.jit`` a training step is
ONE fused XLA program, so there is no per-layer clock to read — but the
compiler knows exactly what each layer costs.  This module reborn-s the
reference's timing hooks the way SURVEY.md §2.3 prescribes: per-layer cost
from compiled-HLO cost analysis, scaled by the measured step time.

How it works:
 1. a recording pass runs the model forward once (eagerly, any input) and
    captures every container child's input via ``Module._probe``;
 2. each leaf layer's ``apply`` (and its value-and-grad, i.e. the cost it
    contributes to a *training* step) is lowered and compiled standalone;
    ``compiled.cost_analysis()['flops']`` is XLA's own number;
 3. the measured wall time of the real fused step is attributed to layers
    proportionally to their compiled training flops, and written into the
    existing ``forward_time``/``backward_time`` fields so ``get_times()``
    (the reference API) reports it.

Also here: ``collective_footprint`` — bytes moved by all-gather /
reduce-scatter / all-reduce / collective-permute in a compiled program,
the analog of the reference's "get weights average" / "aggregate gradient
time" Metrics split (optim/DistriOptimizer.scala:115-213), which measured
the two halves of its BlockManager all-reduce.
"""
from __future__ import annotations

import re
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def _dtype_bytes(name: str) -> int:
    if name.startswith("f8") or name.startswith("s4") or name.startswith("u4"):
        return 1
    return _DTYPE_BYTES.get(name, 4)


def record_layer_inputs(model: Module, x, training: bool = False,
                        rng=None) -> list:
    """Run one eager forward, returning [(parent, index, child, input,
    child_params, child_buffers)] for every container-dispatched child.
    The dispatched params slice is recorded because nested containers'
    OO-shell ``.params`` is None — only the root holds the full tree."""
    model._built()
    records = []

    def probe(parent, idx, child, inp, p, b):
        records.append((parent, idx, child, inp, p, b))

    Module._probe = probe
    try:
        model.apply(model.params, x, buffers=model.buffers,
                    training=training,
                    rng=rng if rng is not None else jax.random.PRNGKey(0))
    finally:
        Module._probe = None
    return records


import os as _os


#: where each planning constant's value actually came from at import
#: time: "env" | "default" | "env-malformed-default".  Consumers that
#: report provenance (models/utils/perf.py's ici_gbps_source) must read
#: THIS, not re-read os.environ at call time — the env can change (or
#: be set malformed) after import without changing the constant.
_ENV_SOURCES: dict = {}


def _env_float(name: str, default: float) -> float:
    """Env override with a loud-but-survivable parse: a malformed value
    must not break `import bigdl_tpu.parallel` for code that never
    touches the roofline numbers.  Read at import time — set the vars
    before importing (they are planning constants, not runtime knobs)."""
    raw = _os.environ.get(name)
    if raw is None:
        _ENV_SOURCES[name] = "default"
        return default
    try:
        value = float(raw)
        _ENV_SOURCES[name] = "env"
        return value
    except ValueError:
        import warnings
        warnings.warn(f"{name}={raw!r} is not a number; using the "
                      f"default {default}")
        _ENV_SOURCES[name] = "env-malformed-default"
        return default


def env_source(name: str) -> str:
    """Provenance of a planning constant as read at import:
    "env", "default", or "env-malformed-default"."""
    return _ENV_SOURCES.get(name, "default")


class DevicePeaks(NamedTuple):
    """Published peak rates of one chip."""
    bf16_flops: float    # FLOP/s, bf16 MXU
    int8_ops: float      # OP/s, int8 MXU
    hbm_bytes_s: float   # bytes/s, HBM
    source: str


#: THE peak table, keyed by ``jax.Device.device_kind``.  A device that is
#: not here is an error (``device_peaks`` raises), never a default: a
#: utilization against somebody else's peak is not a number.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Peaks for ``device_kind`` (default: the attached device's)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)} — add a sourced row to "
            f"utils/profiling.py:DEVICE_PEAKS") from None


def _cost_of_compiled(compiled) -> tuple[float, float]:
    """(flops, bytes accessed) of a compiled program, per XLA."""
    cost = compiled.cost_analysis()
    return (float(cost.get("flops", 0.0) or 0.0),
            float(cost.get("bytes accessed", 0.0) or 0.0))


def _layer_flops(child: Module, params, buffers, inp, training: bool,
                 include_train: bool = True):
    """(fwd flops, train flops, fwd bytes, train bytes) of one layer,
    per XLA cost analysis."""
    rng = jax.random.PRNGKey(0)

    def fwd(p, i):
        y, _ = child.apply(p, i, buffers=buffers, training=training, rng=rng)
        return y

    lowered = jax.jit(fwd).lower(params, inp)
    f_fwd, b_fwd = _cost_of_compiled(lowered.compile())
    if not include_train:
        return f_fwd, f_fwd, b_fwd, b_fwd

    def train(p, i):
        def scalar(pp):
            y = fwd(pp, i)
            leaves = jax.tree_util.tree_leaves(y)
            return sum(jnp.sum(jnp.asarray(l).astype(jnp.float32))
                       for l in leaves)
        loss, grads = jax.value_and_grad(scalar)(p)
        return loss, grads

    try:
        lowered_t = jax.jit(train).lower(params, inp)
        f_train, b_train = _cost_of_compiled(lowered_t.compile())
    except Exception:
        f_train, b_train = f_fwd, b_fwd  # non-differentiable: fwd only
    return f_fwd, f_train, b_fwd, b_train


def profile_layers(model: Module, x, training: bool = True,
                   include_train: bool = True) -> list[dict]:
    """Per-LEAF-layer compiled flops for one forward and one training step.
    Returns [{'module', 'name', 'flops_fwd', 'flops_train'}] in execution
    order.  ``include_train=False`` skips the value-and-grad compile
    (flops_train then mirrors flops_fwd) — half the compile cost when the
    caller only needs forward flops (e.g. pipeline stage balancing)."""
    records = record_layer_inputs(model, x, training=training)
    rows = []
    for parent, idx, child, inp, p, b in records:
        if getattr(child, "modules", None):
            continue  # containers: attributed via their leaves
        try:
            f_fwd, f_train, b_fwd, b_train = _layer_flops(
                child, p, b, inp, training, include_train=include_train)
        except Exception:
            f_fwd = f_train = b_fwd = b_train = 0.0  # XLA folds away
        rows.append({"module": child, "name": child.get_name(),
                     "flops_fwd": f_fwd, "flops_train": f_train,
                     "bytes_fwd": b_fwd, "bytes_train": b_train})
    return rows


def attribute_step_time(model: Module, x, step_time_s: float,
                        training: bool = True,
                        mode: str = "roofline",
                        device_kind: Optional[str] = None) -> list[dict]:
    """Distribute a measured fused-step wall time over layers and write
    the result into each layer's ``forward_time``/``backward_time`` so
    ``get_times()`` — the reference's per-module timing API — reports
    per-layer cost from a *jitted* run.

    ``mode="roofline"`` (default) weighs each layer by
    max(flops/peak FLOP/s, bytes/peak HBM bytes/s) of ``device_kind``
    (default: the attached device; see ``DEVICE_PEAKS``) — a bandwidth-bound
    BatchNorm or transpose is billed for its HBM traffic instead of its
    ~0 flops (which the old flop-share split mis-billed to the convs).
    ``mode="flops"`` keeps the pure flop-proportional split.  Each row
    carries ``bound`` ("compute"/"memory") for roofline mode."""
    if mode not in ("roofline", "flops"):
        raise ValueError(f"mode must be 'roofline'|'flops', got {mode!r}")
    peaks = device_peaks(device_kind) if mode == "roofline" else None
    rows = profile_layers(model, x, training=training)

    def weight(flops, bytes_):
        if mode == "flops":
            return flops
        return max(flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes_s)

    total = sum(weight(r["flops_train"], r["bytes_train"]) for r in rows) or 1.0
    for r in rows:
        w = weight(r["flops_train"], r["bytes_train"])
        t = (w / total) * step_time_s
        if mode == "roofline":
            r["bound"] = ("compute"
                          if r["flops_train"] / peaks.bf16_flops
                          >= r["bytes_train"] / peaks.hbm_bytes_s
                          else "memory")
        # forward/backward split from the compiled fwd vs train weights
        # (the backward ~2x forward rule falls out of the numbers
        # instead of being assumed)
        w_fwd = weight(r["flops_fwd"], r["bytes_fwd"])
        fwd_frac = min(w_fwd / w, 1.0) if w > 0 else 1.0
        r["time_s"] = t
        r["attribution"] = mode
        r["module"].forward_time += t * fwd_frac
        r["module"].backward_time += t * (1.0 - fwd_frac)
    return rows


def measure_layer_times(model: Module, x, training: bool = True,
                        iters: int = 10, warmup: int = 2) -> list[dict]:
    """ACTUAL wall time per layer, measured by executing each leaf layer's
    compiled forward (and, when differentiable, value-and-grad) standalone
    on the current backend (ref nn/abstractnn/AbstractModule.scala:125-135
    accumulates real per-module time the same way, because the reference
    executes layer by layer).

    Honest caveat, stated in the row ("granularity": "standalone"): in the
    real training step XLA fuses layers together, so standalone sums run
    slower than the fused step — use these to RANK layers and find the
    memory/compute balance, and ``attribute_step_time`` (roofline over the
    measured fused step) for shares that add up to the real step time.
    Results are also written into forward_time/backward_time."""
    import time

    records = record_layer_inputs(model, x, training=training)
    rows = []
    for parent, idx, child, inp, p, b in records:
        if getattr(child, "modules", None):
            continue
        rng = jax.random.PRNGKey(0)

        def fwd(pp, i):
            y, _ = child.apply(pp, i, buffers=b, training=training, rng=rng)
            return y

        def train_fn(pp, i):
            def scalar(q):
                leaves = jax.tree_util.tree_leaves(fwd(q, i))
                return sum(jnp.sum(jnp.asarray(l).astype(jnp.float32))
                           for l in leaves)
            return jax.value_and_grad(scalar)(pp)

        def timed(fn):
            try:
                jitted = jax.jit(fn)
                out = None
                for _ in range(warmup):
                    out = jitted(p, inp)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = jitted(p, inp)
                jax.block_until_ready(out)
                # host transfer: block_until_ready alone does not
                # guarantee completion on every backend
                _ = float(jnp.asarray(
                    jax.tree_util.tree_leaves(out)[0]).ravel()[0])
                return (time.perf_counter() - t0) / iters
            except Exception:
                return None

        t_fwd = timed(fwd)
        t_train = timed(train_fn) if training else t_fwd
        row = {"module": child, "name": child.get_name(),
               "measured_fwd_s": t_fwd, "measured_train_s": t_train,
               "granularity": "standalone"}
        rows.append(row)
        if t_fwd is not None:
            child.forward_time += t_fwd
        if t_train is not None and t_fwd is not None:
            child.backward_time += max(t_train - t_fwd, 0.0)
    return rows


def _shape_bytes(shape_str: str) -> int:
    """bytes of an HLO shape literal like 'f32[128,1024]{1,0}' or a tuple
    '(f32[8], f32[8])'."""
    total = 0
    for m in re.finditer(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]", shape_str):
        dtype, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _dtype_bytes(dtype)
    return total


#: v5e ICI: ~45 GB/s per link per direction; ring collectives stream both
#: directions of one axis concurrently, so ~90 GB/s effective per chip is
#: the planning number (the "How to Scale Your Model" recipe: bytes moved /
#: ICI bandwidth = collective time; bytes from the compiled program below).
ICI_GBPS_DEFAULT = _env_float("BIGDL_TPU_ICI_GBPS", 90.0)


def wire_bytes(footprint: dict[str, int], n: int) -> float:
    """Bytes a ring implementation actually moves per chip for the
    collectives in a ``collective_footprint`` dict, on an ``n``-device
    axis.  The footprint records bytes *produced* (HLO result shapes);
    ring algorithms move:

      all-gather:          out × (N-1)/N      (each chip receives the
                                               other N-1 slices)
      reduce-scatter:      in × (N-1)/N = out × (N-1)
      all-reduce:          out × 2(N-1)/N     (reduce-scatter + all-gather)
      collective-permute:  out                (one hop, all bytes)
      all-to-all:          out × (N-1)/N

    With the DP cycle (bf16 all-gather of weights + bf16 reduce-scatter of
    grads, parameters/AllReduceParameter.scala's split) this comes to
    2(N-1)/N x param-bytes — the classic ring all-reduce volume."""
    if n <= 1:
        return 0.0
    factors = {"all-gather": (n - 1) / n, "reduce-scatter": float(n - 1),
               "all-reduce": 2 * (n - 1) / n, "collective-permute": 1.0,
               "all-to-all": (n - 1) / n}
    return float(sum(bytes_ * factors.get(op, 1.0)
                     for op, bytes_ in footprint.items()))


def predict_ici_efficiency(compute_s: float, wire_bytes_per_chip: float,
                           ici_gbps: float = ICI_GBPS_DEFAULT) -> dict:
    """Roofline weak-scaling prediction as an INTERVAL, not a point.

    The truth depends on how much of the collective XLA's latency-hiding
    scheduler hides behind compute, which cannot be known without a
    profile from the target pod; what CAN be known are the two bounds:

      zero overlap:  step = compute + comm   (serial; the floor)
      full overlap:  step = max(compute, comm)  (comm fully hidden; the
                     ceiling — parameters.py:16-17 notes XLA does overlap
                     the DP all-gather with the forward pass in practice)

    ``predicted_efficiency`` stays the conservative zero-overlap bound —
    a claim against a scaling target must hold at the floor."""
    comm_s = wire_bytes_per_chip / (ici_gbps * 1e9)
    step_serial = compute_s + comm_s
    step_overlap = max(compute_s, comm_s)
    eff_lo = compute_s / step_serial if step_serial else 1.0
    eff_hi = compute_s / step_overlap if step_overlap else 1.0
    return {"predicted_comm_s": comm_s, "predicted_step_s": step_serial,
            "predicted_step_s_full_overlap": step_overlap,
            "predicted_efficiency": eff_lo,
            "predicted_efficiency_interval": [eff_lo, eff_hi]}


def collective_footprint(compiled_text: str) -> dict[str, int]:
    """Bytes produced per step by each collective family in an optimized
    HLO dump (``jitted.lower(...).compile().as_text()``).  The all-gather
    row is the reference's getWeights ("get weights average") traffic; the
    reduce-scatter/all-reduce row is putGradients+aggregate ("aggregate
    gradient time") traffic."""
    out = {"all-gather": 0, "reduce-scatter": 0, "all-reduce": 0,
           "collective-permute": 0, "all-to-all": 0}
    for line in compiled_text.splitlines():
        s = line.strip()
        # the shape may carry a TPU layout with parentheses of its own,
        # e.g. bf16[25583592]{0:T(1024)(128)(2,1)S(1)}
        m = re.match(r"^(?:ROOT )?%?[\w.\-]+ = ([^=]*?) (all-gather|"
                     r"reduce-scatter|all-reduce|collective-permute|"
                     r"all-to-all)(-start|-done)?\(", s)
        if not m:
            continue
        shape, op, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue  # the async pair's bytes are counted on -start
        if phase == "-start":
            # async start shapes are (operand..., result...) tuples with
            # one result per operand; count the result half
            shapes = re.findall(r"[a-z][a-z0-9]*\[[\d,]*\](?:\{[\d,]*\})?",
                                shape)
            if shapes:
                shape = " ".join(shapes[len(shapes) // 2:])
        out[op] += _shape_bytes(shape)
    return {k: v for k, v in out.items() if v}
