"""Module system: Torch-style modules compiled to pure JAX functions.

Rebuild of the reference's ``nn/abstractnn/AbstractModule.scala:40-311`` and
``nn/abstractnn/AbstractCriterion.scala:29-55``.  The reference mutates
``output``/``gradInput`` caches and accumulates gradients in place; under
XLA everything must be pure, so each module is split into:

- hyperparameters: plain Python attributes fixed at construction (BigDL
  constructors take explicit dims, so no lazy shape inference is needed);
- ``init(rng) -> params``: a pytree (nested dict) of trainable arrays;
- ``init_buffers() -> buffers``: non-trainable state (e.g. BatchNorm
  running stats), usually ``{}``;
- ``apply(params, x, buffers=..., training=..., rng=...) -> (y, buffers')``:
  the pure forward, traced once per (training,) under ``jax.jit``.

On top of this sits the Torch-style object shell for API parity: ``build``
materializes ``self.params``; ``forward``/``backward`` mirror the
reference's ``updateOutput``/``updateGradInput``+``accGradParameters``
(backward is a ``jax.vjp`` pullback — on TPU there is no hand-written
backward per layer; XLA differentiates the forward).  Training loops use
the functional path (``value_and_grad`` over ``apply``), never ``backward``.

``Activity`` (Tensor ∪ Table, ref nn/abstractnn/Activity.scala:25) needs no
class here: any pytree (array, Table, tuple, dict) is a valid activity.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # pytree of jnp arrays
Buffers = Any
Activity = Any


def _is_array_like(x) -> bool:
    return isinstance(x, (jnp.ndarray, np.ndarray, jax.Array))


class Module:
    """Base module (ref AbstractModule).  Subclasses implement ``init`` and
    either ``f`` (stateless: params, x -> y) or ``apply`` (stateful)."""

    # set by utils.profiling during a shape-recording pass: called as
    # probe(parent, child_index, child, child_input, child_params,
    # child_buffers) from every container dispatch, so per-layer cost
    # attribution sees each layer's actual inputs AND its params slice
    # (nested containers' OO-shell .params is None; only the dispatched
    # slice is real)
    _probe = None

    def __init__(self):
        self._name: Optional[str] = None
        # OO shell state (not used by the functional path)
        self.params: Params = None
        self.buffers: Buffers = {}
        self.grad_params: Params = None
        self.output: Activity = None
        self.grad_input: Activity = None
        self.train: bool = True
        self.forward_time: float = 0.0
        self.backward_time: float = 0.0
        self._jit_cache: dict = {}
        self._rng = None
        self._vjp_fun = None
        self._batch_buckets: Optional[tuple] = None

    # ------------------------------------------------------------------ #
    # functional core                                                    #
    # ------------------------------------------------------------------ #
    def init(self, rng: jax.Array) -> Params:
        """Create trainable parameters. Default: none."""
        return {}

    def init_buffers(self) -> Buffers:
        return {}

    def f(self, params: Params, x: Activity, *, training: bool = False,
          rng: Optional[jax.Array] = None) -> Activity:
        raise NotImplementedError(f"{type(self).__name__} must implement f() or apply()")

    def apply(self, params: Params, x: Activity, *, buffers: Buffers = None,
              training: bool = False, rng: Optional[jax.Array] = None):
        """Pure forward. Returns (output, new_buffers)."""
        y = self.f(params, x, training=training, rng=rng)
        return y, (buffers if buffers is not None else {})

    # ------------------------------------------------------------------ #
    # parameter bookkeeping                                              #
    # ------------------------------------------------------------------ #
    def has_params(self) -> bool:
        leaves = jax.tree_util.tree_leaves(self.init(jax.random.PRNGKey(0))) \
            if self.params is None else jax.tree_util.tree_leaves(self.params)
        return len(leaves) > 0

    def set_name(self, name: str) -> "Module":
        self._name = name
        return self

    def get_name(self) -> str:
        return self._name or type(self).__name__

    # ------------------------------------------------------------------ #
    # Torch-style OO shell                                               #
    # ------------------------------------------------------------------ #
    def build(self, seed: int | jax.Array = 0) -> "Module":
        """Materialize params/buffers on the shell (ref: modules are born
        initialized; here init is explicit because JAX params are pure)."""
        rng = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
        init_rng, self._rng = jax.random.split(rng)
        self.params = self.init(init_rng)
        self.buffers = self.init_buffers()
        # gradient buffers are a second copy of the weights that only the
        # OO training shell reads: made on first use (_grads), never here,
        # so a built model served at GPT-2 XL widths fits one chip
        self.grad_params = None
        return self

    def reset(self, seed: int | jax.Array = 0) -> "Module":
        return self.build(seed)

    def _built(self):
        if self.params is None:
            self.build()
        return self.params

    def _next_rng(self):
        if self._rng is None:
            self._rng = jax.random.PRNGKey(0)
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _jitted_apply(self, training: bool):
        key = ("apply", training)
        if key not in self._jit_cache:
            def run(params, buffers, x, rng):
                # quantized params: expand non-native QTensors here,
                # inside the trace — int8 stays the stored form, the
                # dequant fuses into the consumers (identity for f32
                # trees; see quant/transform.dequantize_entry)
                from bigdl_tpu.quant.transform import dequantize_entry
                params = dequantize_entry(params)
                return self.apply(params, x, buffers=buffers, training=training, rng=rng)
            self._jit_cache[key] = jax.jit(run)
        return self._jit_cache[key]

    def register_batch_buckets(self, buckets: Sequence[int]) -> "Module":
        """Pad eval-mode ``forward`` batches up to these leading-dim
        buckets so a novel batch size within a bucket reuses the cached
        jitted apply instead of retracing (every new leading dim is
        otherwise a fresh trace + XLA compile).  Inference only: the
        training path never pads — zero-filled rows would pollute
        buffer updates (BatchNorm stats) and loss scales.  Pass None to
        unregister.  ``serving.ServingEngine`` is the batched-traffic
        version of the same idea."""
        self._batch_buckets = (tuple(sorted(set(int(b) for b in buckets)))
                               if buckets is not None else None)
        if self._batch_buckets and self._batch_buckets[0] < 1:
            raise ValueError("buckets must be positive ints")
        return self

    def _bucket_batch(self, x) -> Optional[int]:
        """The bucket to pad ``x``'s leading dim to, or None for the
        exact-shape path (training mode, no buckets registered, non-
        array input, or batch larger than the largest bucket)."""
        buckets = getattr(self, "_batch_buckets", None)  # pre-bucket pickles
        if self.train or not buckets or not _is_array_like(x) \
                or getattr(x, "ndim", 0) < 1:
            return None
        n = int(x.shape[0])
        for b in buckets:
            if b >= n:
                return b if b != n else None  # exact hit: no pad needed
        return None

    def forward(self, x: Activity) -> Activity:
        """Stateful forward (ref AbstractModule.forward:144-150, with timing)."""
        self._built()
        t0 = time.perf_counter()
        rng = self._next_rng()
        bucket = self._bucket_batch(x)
        if bucket is not None:
            n = int(x.shape[0])
            pad = jnp.zeros((bucket - n,) + tuple(x.shape[1:]), x.dtype)
            xp = jnp.concatenate([jnp.asarray(x), pad], axis=0)
            y, _ = self._jitted_apply(self.train)(self.params, self.buffers, xp, rng)
            y = jax.tree_util.tree_map(
                lambda a: a[:n] if (_is_array_like(a)
                                    and getattr(a, "ndim", 0) >= 1
                                    and a.shape[0] == bucket) else a, y)
        else:
            y, new_buffers = self._jitted_apply(self.train)(self.params, self.buffers, x, rng)
            if self.train:
                self.buffers = new_buffers
        self.output = y
        self.forward_time += time.perf_counter() - t0
        return y

    def update_output(self, x: Activity) -> Activity:
        return self.forward(x)

    def backward(self, x: Activity, grad_output: Activity) -> Activity:
        """Stateful backward: computes gradInput AND accumulates parameter
        gradients (ref AbstractModule.backward:162-169 = updateGradInput +
        accGradParameters).  Implemented as one ``jax.vjp`` pullback over
        (params, input) — XLA derives what the reference hand-writes."""
        self._built()
        t0 = time.perf_counter()
        rng = self._next_rng()
        training = self.train

        key = ("vjp", training)
        if key not in self._jit_cache:
            def run(params, inp, g, buffers, rng_):
                def fwd(p, i):
                    y, _ = self.apply(p, i, buffers=buffers, training=training, rng=rng_)
                    return y
                _, pullback = jax.vjp(fwd, params, inp)
                return pullback(g)
            self._jit_cache[key] = jax.jit(run)
        grad_p, grad_in = self._jit_cache[key](self.params, x, grad_output, self.buffers, rng)
        if self.grad_params is None:
            self.grad_params = grad_p
        else:
            self.grad_params = jax.tree_util.tree_map(jnp.add, self.grad_params, grad_p)
        self.grad_input = grad_in
        self.backward_time += time.perf_counter() - t0
        return grad_in

    def update_grad_input(self, x: Activity, grad_output: Activity) -> Activity:
        """Gradient w.r.t. input only (no param-grad accumulation)."""
        self._built()
        rng = self._next_rng()
        training = self.train

        def fwd(inp):
            y, _ = self.apply(self.params, inp, buffers=self.buffers, training=training, rng=rng)
            return y

        _, pullback = jax.vjp(fwd, x)
        (grad_in,) = pullback(grad_output)
        self.grad_input = grad_in
        return grad_in

    def acc_grad_parameters(self, x: Activity, grad_output: Activity) -> None:
        self._built()
        rng = self._next_rng()
        training = self.train

        def fwd(params):
            y, _ = self.apply(params, x, buffers=self.buffers, training=training, rng=rng)
            return y

        _, pullback = jax.vjp(fwd, self.params)
        (grad_p,) = pullback(grad_output)
        if self.grad_params is None:
            self.grad_params = grad_p
        else:
            self.grad_params = jax.tree_util.tree_map(jnp.add, self.grad_params, grad_p)

    def zero_grad_parameters(self) -> None:
        if self.params is not None:
            self.grad_params = jax.tree_util.tree_map(jnp.zeros_like, self.params)

    def _grads(self) -> Params:
        """Accumulated parameter gradients; zeros before any backward."""
        if self.grad_params is None:
            self.zero_grad_parameters()
        return self.grad_params

    def parameters(self):
        """(weights, gradWeights) as parallel leaf lists (ref :227)."""
        self._built()
        w = jax.tree_util.tree_leaves(self.params)
        g = jax.tree_util.tree_leaves(self._grads())
        return w, g

    def get_parameters(self):
        """Flatten all params (and grads) each into ONE contiguous vector
        (ref getParameters/Module.flatten, nn/Module.scala:41 — the
        flattened-storage trick becomes pytree ravel)."""
        from jax.flatten_util import ravel_pytree
        self._built()
        flat_w, unravel = ravel_pytree(self.params)
        flat_g, _ = ravel_pytree(self._grads())
        return flat_w, flat_g, unravel

    def get_parameters_table(self):
        """name -> {weight, bias, gradWeight, gradBias} (ref :242)."""
        from bigdl_tpu.utils.table import T
        self._built()
        table = T()
        self._collect_param_table(table, self.get_name(), self.params, self._grads())
        return table

    def _collect_param_table(self, table, name, params, grads):
        from bigdl_tpu.utils.table import T
        if isinstance(params, dict) and params:
            entry = T()
            for k, v in params.items():
                if _is_array_like(v):
                    entry[k] = v
                    gv = grads[k] if grads is not None and k in grads else None
                    entry["grad" + k[0].upper() + k[1:]] = gv
            if len(entry):
                table[name] = entry

    # -- mode/flags ----------------------------------------------------- #
    def training(self) -> "Module":
        self.train = True
        return self

    def evaluate(self) -> "Module":
        self.train = False
        return self

    def is_training(self) -> bool:
        return self.train

    # -- timing (ref :125-135) ------------------------------------------ #
    def get_times(self):
        return [(self, self.forward_time, self.backward_time)]

    def reset_times(self) -> None:
        self.forward_time = 0.0
        self.backward_time = 0.0

    def clear_state(self) -> "Module":
        self.output = None
        self.grad_input = None
        return self

    # -- (de)materialization -------------------------------------------- #
    def clone_module(self) -> "Module":
        """Clone sharing nothing (ref cloneModule via java ser, :284)."""
        import copy
        new = copy.copy(self)
        new._jit_cache = {}
        new.params = jax.tree_util.tree_map(lambda a: a, self.params) if self.params is not None else None
        new.buffers = jax.tree_util.tree_map(lambda a: a, self.buffers)
        new.grad_params = jax.tree_util.tree_map(lambda a: a, self.grad_params) if self.grad_params is not None else None
        return new

    def save(self, path: str, overwrite: bool = False) -> "Module":
        from bigdl_tpu.utils import file_io
        file_io.save_module(self, path, overwrite=overwrite)
        return self

    @staticmethod
    def load(path: str, template: "Optional[Module]" = None) -> "Module":
        """Load a saved module.  Pass ``template`` (a code-constructed
        instance of the architecture) to restore arrays into it without
        consulting the checkpoint's class names — immune to renames."""
        from bigdl_tpu.utils import file_io
        return file_io.load_module(path, template=template)

    def save_torch(self, path: str, overwrite: bool = False) -> "Module":
        """Write a Torch7-readable .t7 (ref AbstractModule.saveTorch)."""
        from bigdl_tpu.utils import torch_file
        torch_file.save_model(self, path, overwrite=overwrite)
        return self

    @staticmethod
    def load_torch(path: str) -> "Module":
        """Load a Torch7 .t7 model (ref Module.loadTorch, nn/Module.scala:31)."""
        from bigdl_tpu.utils import torch_file
        return torch_file.load_model(path)

    def load_caffe(self, def_path: str, model_path: str,
                   match_all: bool = True) -> "Module":
        """Copy caffe blobs into this model's same-named modules
        (ref Module.loadCaffe, nn/Module.scala:35-39)."""
        from bigdl_tpu.utils import caffe_loader
        self._built()
        return caffe_loader.load(self, def_path, model_path, match_all)

    def save_pytorch(self, path) -> "Module":
        """Write this model's params/buffers as a ``torch.save``d
        PyTorch-convention state dict.  The file round-trips through
        ``load_pytorch``; loading it into an actual torch module needs
        a positional key rename plus ``strict=False`` (we emit no
        ``num_batches_tracked``), and recurrent cells export our fused
        layout, which torch RNN modules cannot consume (see
        utils/torch_import.export_torch_state_dict)."""
        import torch
        from bigdl_tpu.utils import torch_import
        sd = torch_import.export_torch_state_dict(self)
        # np.array: forced writable copy — jax-backed arrays are
        # read-only views torch.from_numpy warns about and documents
        # mutating as UB
        torch.save({k: torch.from_numpy(np.array(v))
                    for k, v in sd.items()}, path)
        return self

    def load_pytorch(self, state_dict_or_path, strict: bool = True) -> "Module":
        """Import a PyTorch state dict (or a ``torch.save``d checkpoint
        path) into this model — the modern pretrained-import path (ref
        example/loadmodel/ModelValidator.scala's role; see
        utils/torch_import.py for the positional mapping contract)."""
        import os
        from bigdl_tpu.utils import torch_import
        self._built()
        if isinstance(state_dict_or_path, (str, bytes, os.PathLike)):
            return torch_import.load_torch_checkpoint(
                self, state_dict_or_path, strict=strict)
        return torch_import.load_torch_state_dict(
            self, state_dict_or_path, strict=strict)

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_jit_cache"] = {}  # jitted callables are not picklable
        state["_vjp_fun"] = None
        return state

    def serve(self, **kwargs) -> "Any":
        """This built module as a servable endpoint — see
        :class:`bigdl_tpu.serving.ServingEngine` for the knobs
        (buckets, max_batch_size, max_wait_ms, backpressure)."""
        from bigdl_tpu.serving import ServingEngine
        self._built()
        return ServingEngine(self, **kwargs)

    def quantize(self, dtype: str = "int8", *, policy=None,
                 compute: Optional[str] = None) -> "Module":
        """Weight-only quantized EVAL-MODE clone of this built module
        (``self`` keeps its f32 params untouched — both replicas can be
        served side by side, the compile cache keys them apart).

        ``dtype="int8"``: eligible weights become
        :class:`~bigdl_tpu.quant.QTensor` (int8 + per-channel f32
        scales).  ``compute`` picks the kernel regime: the default
        ``"dequant"`` dequantizes on the fly inside the MXU kernel
        (bf16 operands, f32 accumulation); ``"int8"`` quantizes
        activations per token and feeds BOTH int8 operands to the MXU
        with exact int32 accumulation and one f32 rescale; ``"fp8"``
        gates on capable device kinds.  ``dtype="bf16"``: a
        plain storage cast.  The include/exclude ``policy`` defaults
        skip norms, biases and embedding tables (see quant.QuantPolicy);
        an explicit ``policy`` wins over ``compute``.

        The clone is inference-only: its int8 leaves are not
        differentiable, so train on the f32 original and re-quantize.
        Byte savings, per-layer max abs dequant error and (for int8
        compute) the int32-accumulator overflow-risk gauge are published
        as ``quant/*`` gauges on the obs registry and kept on
        ``clone.quant_report``.
        """
        from bigdl_tpu.obs import get_registry
        from bigdl_tpu.quant import QuantPolicy, quantize_params
        self._built()
        if policy is None and compute is not None:
            policy = QuantPolicy(dtype, compute=compute)
        report: dict = {}
        new = self.clone_module()
        new.params = quantize_params(self.params, dtype, policy=policy,
                                     module=self, report=report)
        new.grad_params = None  # int8 leaves are not differentiable
        new.quant_report = report
        reg = get_registry()
        reg.gauge("quant/bytes_saved", unit="B").set(report["bytes_saved"])
        reg.gauge("quant/payload_ratio").set(report["payload_ratio"])
        reg.gauge("quant/max_abs_dequant_error").set(
            report["max_abs_dequant_error"])
        for path, err in report["per_layer_max_abs_err"].items():
            reg.gauge(f"quant/max_abs_dequant_error/{path}").set(err)
        if report.get("per_layer_overflow_risk"):
            reg.gauge("quant/overflow_risk").set(report["overflow_risk"])
            for path, risk in report["per_layer_overflow_risk"].items():
                reg.gauge(f"quant/overflow_risk/{path}").set(risk)
        return new.evaluate()

    def __repr__(self) -> str:
        return f"{type(self).__name__}"

    # predict / evaluate conveniences are provided by optim.* and models.*


class Criterion:
    """Loss base (ref AbstractCriterion).  Subclasses implement
    ``loss(output, target) -> scalar`` as a pure function."""

    def __init__(self):
        self.output: Optional[jnp.ndarray] = None
        self.grad_input: Activity = None
        self._jit_cache: dict = {}

    def loss(self, output: Activity, target: Activity) -> jnp.ndarray:
        raise NotImplementedError

    def _flat_time_reduction(self) -> Optional[str]:
        """How this loss reduces a batch, IF flattening extra leading
        structure into the batch dim is value-equivalent: "mean" /
        "sum", or None when it is not (e.g. per-call weighted
        normalization).  TimeDistributedCriterion uses this to evaluate
        (B, T, ...) as one (B*T, ...) call instead of tracing T
        per-timestep calls — at long context the unrolled trace is
        O(T) compile time and HLO size."""
        return None

    # functional aliases
    def apply(self, output: Activity, target: Activity) -> jnp.ndarray:
        return self.loss(output, target)

    # Torch-style shell
    def forward(self, output: Activity, target: Activity) -> jnp.ndarray:
        if "fwd" not in self._jit_cache:
            self._jit_cache["fwd"] = jax.jit(self.loss)
        self.output = self._jit_cache["fwd"](output, target)
        return self.output

    def backward(self, output: Activity, target: Activity) -> Activity:
        if "bwd" not in self._jit_cache:
            self._jit_cache["bwd"] = jax.jit(
                lambda o, t: jax.grad(lambda oo: self.loss(oo, t).sum())(o)
            )
        self.grad_input = self._jit_cache["bwd"](output, target)
        return self.grad_input

    def update_output(self, output, target):
        return self.forward(output, target)

    def update_grad_input(self, output, target):
        return self.backward(output, target)

    def clone_criterion(self) -> "Criterion":
        import copy
        new = copy.copy(self)
        new._jit_cache = {}
        return new

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_jit_cache"] = {}
        return state

    def __repr__(self) -> str:
        return f"{type(self).__name__}"
