"""Module / object persistence (ref utils/File.scala:26-122 — java
serialization with hdfs: support).

Two deliberate upgrades over a naive pickle:

1. **Remote-capable**: every read/write flows through
   ``bigdl_tpu.utils.fs`` so ``gs://`` / ``hdfs://`` / ``memory://`` paths
   work wherever a local path does (pod workers cannot checkpoint to
   local disk; the reference has the same property via hdfs:).
2. **No live objects in checkpoints**: the on-disk format (version 1) is
   a dict of plain builtins + numpy arrays — a *spec* describing each
   module (class path + hyperparameter state + children) plus the
   param/buffer array trees.  Pickled live modules break on any class
   rename/refactor; arrays + a declarative spec survive, and
   ``load_module(path, template=...)`` restores into caller-constructed
   architecture without consulting the spec's class names at all.

The orbax-style training checkpoints live in ``bigdl_tpu.optim``; this is
the ``Module.save`` / ``Module.load`` whole-model path
(ref nn/Module.scala:27-39).
"""
from __future__ import annotations

import importlib
import pickle
from typing import Any, Optional

import jax
import numpy as np

from bigdl_tpu.utils import fs

FORMAT = "bigdl_tpu.module"
VERSION = 1

_PLAIN = (int, float, bool, str, bytes, type(None), np.ndarray, np.generic)
# OO-shell state that is NOT a hyperparameter (rebuilt fresh on load)
_SHELL_ATTRS = {"params", "buffers", "grad_params", "output", "grad_input",
                "forward_time", "backward_time", "modules"}
_SHELL_PREFIXES = ("_jit", "_rng", "_vjp", "_fwd", "_step")


def _to_host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a), tree)


def _is_plain(v) -> bool:
    if isinstance(v, _PLAIN):
        return True
    if isinstance(v, (tuple, list)):
        return all(_is_plain(x) for x in v)
    if isinstance(v, dict):
        return all(isinstance(k, (str, int)) and _is_plain(x)
                   for k, x in v.items())
    return False


def _class_path(cls: type) -> str:
    return f"{cls.__module__}:{cls.__qualname__}"


def _resolve_class(path: str) -> type:
    mod, _, qual = path.partition(":")
    obj = importlib.import_module(mod)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _encode_value(v):
    from bigdl_tpu.nn.module import Criterion, Module

    if isinstance(v, jax.Array):
        return np.asarray(v)  # device arrays persist as host numpy
    if isinstance(v, Module):
        return {"__kind__": "module", **module_spec(v)}
    if isinstance(v, Criterion):
        return {"__kind__": "object", "class": _class_path(type(v)),
                "state": _encode_state(v.__dict__)}
    if isinstance(v, type):
        return {"__kind__": "class", "class": _class_path(v)}
    if isinstance(v, (tuple, list)):
        kind = "tuple" if isinstance(v, tuple) else "list"
        if _is_plain(v):
            return v
        return {"__kind__": kind, "items": [_encode_value(x) for x in v]}
    if isinstance(v, dict) and not _is_plain(v):
        return {"__kind__": "dict",
                "items": {k: _encode_value(x) for k, x in v.items()}}
    if _is_plain(v):
        return v
    raise TypeError(
        f"cannot serialize hyperparameter of type {type(v).__name__}; "
        f"only builtins, numpy arrays, classes, Modules and Criterions "
        f"belong in module state")


def _decode_value(v):
    if isinstance(v, dict) and "__kind__" in v:
        kind = v["__kind__"]
        if kind == "module":
            return build_module(v)
        if kind == "object":
            cls = _resolve_class(v["class"])
            obj = cls.__new__(cls)
            obj.__dict__.update(_decode_state(v["state"]))
            # criterion shells carry a jit cache; rebuild empty
            if not hasattr(obj, "_jit_cache"):
                obj._jit_cache = {}
            return obj
        if kind == "class":
            return _resolve_class(v["class"])
        if kind == "tuple":
            return tuple(_decode_value(x) for x in v["items"])
        if kind == "list":
            return [_decode_value(x) for x in v["items"]]
        if kind == "dict":
            return {k: _decode_value(x) for k, x in v["items"].items()}
        raise ValueError(f"unknown encoded kind {kind!r}")
    return v


def _encode_state(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if k in _SHELL_ATTRS or any(k.startswith(p) for p in _SHELL_PREFIXES):
            continue
        from bigdl_tpu.nn.module import Criterion, Module
        if (callable(v) and not isinstance(v, (type, Module, Criterion))):
            if k.startswith("_"):
                continue  # private machinery (caches etc.), rebuilt lazily
            raise TypeError(
                f"cannot serialize callable hyperparameter {k!r} "
                f"({type(v).__name__}); persistence would silently drop "
                f"it — hold a Module/class instead of a bare function")
        out[k] = _encode_value(v)
    return out


def _decode_state(d: dict) -> dict:
    return {k: _decode_value(v) for k, v in d.items()}


def module_spec(module) -> dict:
    """Declarative description: class path + hyperparameter state +
    children.  Contains no class objects or live instances."""
    spec = {"class": _class_path(type(module)),
            "state": _encode_state(module.__dict__)}
    children = getattr(module, "modules", None)
    if children is not None:
        spec["children"] = [module_spec(m) for m in children]
    return spec


def build_module(spec: dict):
    """Instantiate a module tree from its spec (no saved class references
    are executed — classes resolve by name against the current code)."""
    from bigdl_tpu.nn.module import Module

    cls = _resolve_class(spec["class"])
    obj = cls.__new__(cls)
    Module.__init__(obj)  # baseline shell state
    obj.__dict__.update(_decode_state(spec["state"]))
    if "children" in spec:
        obj.modules = [build_module(s) for s in spec["children"]]
    return obj


# --------------------------------------------------------------------- #
# generic object IO (driver state tables etc. — plain data only)        #
# --------------------------------------------------------------------- #
def save(obj: Any, path: str, overwrite: bool = False) -> None:
    if fs.exists(path) and not overwrite:
        raise FileExistsError(f"{path} exists; pass overwrite=True")
    fs.atomic_write(path, pickle.dumps(obj))


def load(path: str) -> Any:
    with fs.open_file(path, "rb") as f:
        return pickle.load(f)


def latest_checkpoint(directory: str):
    """Newest ``model.<n>`` / ``state.<n>`` pair written by
    ``Optimizer.set_checkpoint`` under ``directory`` (any fs scheme), as
    ``(model_path, state_path, n)`` — or None when the directory holds no
    complete pair.  The resume counterpart of the reference's
    checkpoint-and-restart cycle (models/lenet/Train.scala:55-68 loads
    model.<n> + state.<n> by hand)."""
    try:
        names = fs.listdir(directory)
    except FileNotFoundError:
        return None  # no checkpoints yet; scheme/permission errors raise
    models, states = set(), set()
    for name in names:
        stem, _, idx = name.partition(".")
        if not idx.isdigit():
            continue
        if stem == "model":
            models.add(int(idx))
        elif stem == "state":
            states.add(int(idx))
    complete = sorted(models & states)
    if not complete:
        return None
    n = complete[-1]
    return (fs.join(directory, f"model.{n}"),
            fs.join(directory, f"state.{n}"), n)


# --------------------------------------------------------------------- #
# module IO                                                             #
# --------------------------------------------------------------------- #
def save_module(module, path: str, overwrite: bool = False) -> None:
    """Persist spec + params + buffers (format v1, no live objects)."""
    state = {
        "format": FORMAT,
        "version": VERSION,
        "spec": module_spec(module),
        "params": _to_host(module.params),
        "buffers": _to_host(module.buffers),
    }
    save(state, path, overwrite=overwrite)


def load_module(path: str, template=None):
    """Load a saved module.

    With ``template`` (an un/re-built instance of the architecture), the
    arrays are restored into it and the stored spec is ignored — this
    path is immune to class renames.  Without a template the spec rebuilds
    the tree by class name.  Old (round-1) checkpoints that pickled the
    live module still load.
    """
    state = load(path)
    if not (isinstance(state, dict) and state.get("format") == FORMAT):
        # legacy format: {"module": <pickled Module>, "params", "buffers"}
        module = state["module"]
        module.params = jax.tree_util.tree_map(lambda a: a, state["params"])
        module.buffers = state["buffers"]
        return module
    if state["version"] > VERSION:
        raise ValueError(f"checkpoint version {state['version']} is newer "
                         f"than this library ({VERSION})")
    module = template if template is not None else build_module(state["spec"])
    params = state["params"]
    if template is not None:
        # structure + shape check without materializing a throwaway init
        ref = jax.eval_shape(module.init, jax.random.PRNGKey(0))
        want = jax.tree_util.tree_structure(ref)
        got = jax.tree_util.tree_structure(params)
        if want != got:
            raise ValueError(
                f"checkpoint param tree does not match template: "
                f"{got} vs {want}")
        for (kp, r), l in zip(
                jax.tree_util.tree_flatten_with_path(ref)[0],
                jax.tree_util.tree_leaves(params)):
            if tuple(r.shape) != tuple(np.shape(l)):
                name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                                for p in kp)
                raise ValueError(
                    f"checkpoint param {name} has shape {np.shape(l)}, "
                    f"template expects {tuple(r.shape)}")
    module.params = params
    module.buffers = state["buffers"]
    return module
