"""Block-size autotuning and crossover cache for the attention kernels.

The reference ships MKL-tuned primitives per CPU generation (SURVEY.md
§2.1); the TPU analogue is this module: measure the Pallas kernels
against the naive-XLA baseline on the device actually attached, persist
the winners per ``device_kind``, and let the dispatchers consult the
cache instead of a hard-coded block size.  Two families are tuned:

* **flash train step** — sweeps ``(block_q, block_k)`` per
  ``(seq_len, head_dim, dtype, causal)``, timing a real fwd+bwd train
  step of the flash kernel at each candidate plus one naive-XLA baseline
  row.  The winner entry records the best blocks AND the crossover
  verdict ``use_flash`` (flash only when it measured faster than XLA —
  or when XLA could not run the shape at all).
* **paged decode** — times ``ops.paged_attention`` against the dense
  ``kc[tables]`` gather per ``(head_dim, block_len, dtype)`` so
  ``LMServingEngine``'s "auto" decode dispatch is measurement-backed.
* **qcompute duel** — times the true int8xint8 MXU matmul
  (``quant.kernels.qmatmul_i8``: per-token activation quant, int32
  accumulation, one f32 rescale) against the dequant-bf16 baseline per
  ``(m, k, n)`` activation/weight shape, so ``QuantPolicy
  (compute="auto")`` resolves to int8 only where it measured faster —
  the same never-lose-to-the-baseline contract as the other families.

The cache is a resumable measurement artifact like every other tool in
this repo (TUNE_ATTN.json, committed): a row is flushed after every
candidate, ``complete`` stays false until the final flush, and a rerun
reuses only rows whose full identity (platform, device_kind, candidate
key, batch/heads/iters) matches — mismatched rows are re-measured.
A rerun over a ``complete: true`` doc for the same platform/device
kind does not touch the file until a candidate actually re-measures,
so a timeout-killed all-reuse pass cannot regress the certification.
Rows from OTHER configs on the same device accumulate across runs, so
the cache grows one sweep at a time across chip calls.
"""
from __future__ import annotations

import os
import time
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: default (block_q, block_k) sweep grid; trimmed CLIs may pass fewer
DEFAULT_GRID: Tuple[Tuple[int, int], ...] = (
    (128, 128), (128, 256), (128, 512),
    (256, 256), (256, 512), (512, 512),
)

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# substrings that mark a candidate as impossible-at-this-shape rather
# than transiently failed: such rows are reusable (skip re-measuring a
# known-OOM block size) and count as an XLA forfeit in the crossover
_CAPACITY_PAT = ("RESOURCE_EXHAUSTED", "out of memory", "OOM", "vmem",
                 "VMEM", "Mosaic", "too large", "exceeds")


def _is_capacity_error(row) -> bool:
    err = row.get("error") or ""
    return any(p in err for p in _CAPACITY_PAT)


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


def _device_kind() -> Optional[str]:
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return None


def cache_path() -> str:
    """TUNE_ATTN.json at the repo root unless BIGDL_TPU_TUNE_CACHE says
    otherwise (tests point it at tmp files)."""
    return (os.environ.get("BIGDL_TPU_TUNE_CACHE")
            or os.path.join(_REPO_ROOT, "TUNE_ATTN.json"))


def attention_key(seq_len: int, head_dim: int, dtype, causal: bool) -> str:
    return "t%d_d%d_%s_%s" % (int(seq_len), int(head_dim),
                              _dtype_name(dtype),
                              "causal" if causal else "full")


def paged_key(head_dim: int, block_len: int, dtype) -> str:
    return "paged_d%d_b%d_%s" % (int(head_dim), int(block_len),
                                 _dtype_name(dtype))


def qcompute_key(m: int, k: int, n: int) -> str:
    return "qcompute_m%d_k%d_n%d" % (int(m), int(k), int(n))


def parse_grid(spec: str) -> Tuple[Tuple[int, int], ...]:
    """"128:128,256:512" -> ((128, 128), (256, 512))."""
    out = []
    for part in spec.split(","):
        bq, bk = part.strip().split(":")
        out.append((int(bq), int(bk)))
    return tuple(out)


# ---------------------------------------------------------------------------
# cache lookup (the dispatcher side)

_memo = {"key": None, "doc": None}


def clear_cache() -> None:
    """Drop the in-memory cache memo (tests; after external writes)."""
    _memo["key"] = None
    _memo["doc"] = None


def load_cache(path: Optional[str] = None):
    """The parsed TUNE_ATTN doc, memoized on (path, mtime, size) so
    trace-time lookups cost one os.stat, not a JSON parse."""
    path = path or cache_path()
    try:
        st = os.stat(path)
    except OSError:
        return None
    key = (path, st.st_mtime_ns, st.st_size)
    if _memo["key"] == key:
        return _memo["doc"]
    from bigdl_tpu.utils.artifacts import load_artifact
    doc = load_artifact(path)
    _memo["key"] = key
    _memo["doc"] = doc
    return doc


class TunedAttention(NamedTuple):
    block_q: Optional[int]
    block_k: Optional[int]
    use_flash: Optional[bool]  # None: no XLA baseline measured yet
    flash_step_s: Optional[float]
    xla_step_s: Optional[float]


class TunedPagedDecode(NamedTuple):
    use_kernel: Optional[bool]
    kernel_step_s: Optional[float]
    gather_step_s: Optional[float]


def lookup(seq_len: int, head_dim: int, dtype, causal: bool,
           *, path: Optional[str] = None) -> Optional[TunedAttention]:
    """Tuned winner for one flash config, or None when the cache has no
    verdict FOR THE ATTACHED DEVICE KIND (a cache tuned on another chip
    generation — or on CPU — must never steer this one)."""
    doc = load_cache(path)
    if not isinstance(doc, dict) or doc.get("device_kind") != _device_kind():
        return None
    w = (doc.get("winners") or {}).get(
        attention_key(seq_len, head_dim, dtype, causal))
    if not isinstance(w, dict):
        return None
    return TunedAttention(w.get("block_q"), w.get("block_k"),
                          w.get("use_flash"),
                          w.get("flash_step_s"), w.get("xla_step_s"))


def lookup_paged(head_dim: int, block_len: int, dtype,
                 *, path: Optional[str] = None) -> Optional[TunedPagedDecode]:
    """Tuned kernel-vs-gather verdict for the paged decode attention."""
    doc = load_cache(path)
    if not isinstance(doc, dict) or doc.get("device_kind") != _device_kind():
        return None
    w = (doc.get("winners") or {}).get(paged_key(head_dim, block_len, dtype))
    if not isinstance(w, dict):
        return None
    return TunedPagedDecode(w.get("use_kernel"),
                            w.get("kernel_step_s"), w.get("gather_step_s"))


def lookup_qcompute(m: int, k: int, n: int,
                    *, path: Optional[str] = None) -> Optional[str]:
    """Measured winner of the int8-compute-vs-dequant duel for an
    ``(m, k, n)`` matmul on THE ATTACHED device kind: "int8", "dequant",
    or None when there is no verdict (``compute="auto"`` treats None as
    dequant, so auto can never lose to the baseline).  An exact (m, k,
    n) entry wins; otherwise the verdict of the largest-m entry with the
    same (k, n) applies — m is the token batch, which varies run to run,
    while (k, n) is the layer geometry the duel was tuned for."""
    doc = load_cache(path)
    if not isinstance(doc, dict) or doc.get("device_kind") != _device_kind():
        return None
    winners = doc.get("winners") or {}
    w = winners.get(qcompute_key(m, k, n))
    if isinstance(w, dict) and w.get("use_int8") is not None:
        return "int8" if w["use_int8"] else "dequant"
    best = None
    for entry in winners.values():
        if (isinstance(entry, dict) and entry.get("qcompute")
                and entry.get("k") == int(k) and entry.get("n") == int(n)
                and entry.get("use_int8") is not None):
            if best is None or entry.get("m", 0) > best.get("m", 0):
                best = entry
    if best is None:
        return None
    return "int8" if best["use_int8"] else "dequant"


# ---------------------------------------------------------------------------
# winner recomputation (from ALL rows, every flush)

def _row_key(r) -> tuple:
    if r.get("kind") == "qcompute":
        return ("qcompute", r.get("impl"), r.get("m"), r.get("k"),
                r.get("n"))
    if r.get("kind") == "paged_decode":
        return ("paged_decode", r.get("impl"), r.get("slots"),
                r.get("heads"), r.get("head_dim"), r.get("cache_len"),
                r.get("block_len"), r.get("dtype"))
    return ("train_step", r.get("impl"), r.get("seq_len"),
            r.get("head_dim"), r.get("dtype"),
            bool(r.get("causal", True)), r.get("block_q"), r.get("block_k"))


def _recompute_winners(rows) -> dict:
    winners = {}
    att, paged, qcomp = {}, {}, {}
    for r in rows:
        if not isinstance(r, dict):
            continue
        if r.get("kind") == "qcompute":
            cfg = (r.get("m"), r.get("k"), r.get("n"))
            qcomp.setdefault(cfg, []).append(r)
        elif r.get("kind") == "paged_decode":
            cfg = (r.get("head_dim"), r.get("block_len"), r.get("dtype"))
            paged.setdefault(cfg, []).append(r)
        elif r.get("kind") == "train_step":
            cfg = (r.get("seq_len"), r.get("head_dim"), r.get("dtype"),
                   bool(r.get("causal", True)))
            att.setdefault(cfg, []).append(r)
    for (t, d, dt, causal), rs in sorted(att.items(), key=str):
        flash = [r for r in rs if r.get("impl") == "flash" and "step_s" in r]
        xla = [r for r in rs if r.get("impl") == "naive_xla"
               and "step_s" in r]
        xla_forfeit = any(r.get("impl") == "naive_xla"
                          and _is_capacity_error(r) for r in rs)
        entry = {"seq_len": t, "head_dim": d, "dtype": dt, "causal": causal}
        if flash:
            best = min(flash, key=lambda r: r["step_s"])
            entry["block_q"] = best.get("block_q")
            entry["block_k"] = best.get("block_k")
            entry["flash_step_s"] = best["step_s"]
        if xla:
            entry["xla_step_s"] = min(r["step_s"] for r in xla)
        if flash and xla:
            entry["use_flash"] = entry["flash_step_s"] < entry["xla_step_s"]
            entry["flash_speedup_vs_xla"] = round(
                entry["xla_step_s"] / entry["flash_step_s"], 4)
        elif flash and xla_forfeit:
            entry["use_flash"] = True  # XLA cannot even run the shape
        else:
            entry["use_flash"] = None
        winners[attention_key(t, d, dt, causal)] = entry
    for (d, bl, dt), rs in sorted(paged.items(), key=str):
        by = {}
        for r in rs:
            if "step_s" in r:
                prev = by.get(r.get("impl"))
                if prev is None or r["step_s"] < prev:
                    by[r.get("impl")] = r["step_s"]
        entry = {"head_dim": d, "block_len": bl, "dtype": dt}
        kern, gath = by.get("paged_kernel"), by.get("dense_gather")
        if kern is not None:
            entry["kernel_step_s"] = kern
        if gath is not None:
            entry["gather_step_s"] = gath
        if kern is not None and gath is not None:
            entry["use_kernel"] = kern < gath
            entry["kernel_speedup_vs_gather"] = round(gath / kern, 4)
        else:
            entry["use_kernel"] = None
        winners[paged_key(d, bl, dt)] = entry
    for (m, k, n), rs in sorted(qcomp.items(), key=str):
        by = {}
        for r in rs:
            if "step_s" in r:
                prev = by.get(r.get("impl"))
                if prev is None or r["step_s"] < prev:
                    by[r.get("impl")] = r["step_s"]
        entry = {"qcompute": True, "m": m, "k": k, "n": n}
        i8, dq = by.get("int8_compute"), by.get("dequant_bf16")
        if i8 is not None:
            entry["int8_step_s"] = i8
        if dq is not None:
            entry["dequant_step_s"] = dq
        if i8 is not None and dq is not None:
            # strict <: a tie keeps the baseline (auto never loses)
            entry["use_int8"] = i8 < dq
            entry["int8_speedup_vs_dequant"] = round(dq / i8, 4)
        else:
            entry["use_int8"] = None
        winners[qcompute_key(m, k, n)] = entry
    return winners


# ---------------------------------------------------------------------------
# measurement

def _train_step_time(fn, q, k, v, iters: int) -> float:
    """Mean seconds per fwd+bwd train step (compile excluded, hard sync
    via a host read — device_put alone would time the dispatch, not the
    compute)."""
    g = jax.jit(jax.grad(
        lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    out = g(q, k, v)
    float(out[0].astype(jnp.float32).sum())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(q, k, v)
    float(out[0].astype(jnp.float32).sum())
    return (time.perf_counter() - t0) / iters


def _op_step_time(fn, args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _run_sweep(cands, measure, run_match, *, path, finalize, log):
    """Shared resumable candidate loop: reuse identity-matched prior
    rows, re-measure the rest, flush the artifact (rows + recomputed
    winners) after EVERY candidate so a killed sweep resumes — except
    that a certified complete doc is never rewritten before the first
    genuinely new measurement lands."""
    from bigdl_tpu.utils.artifacts import load_artifact, write_artifact
    plat = jax.default_backend()
    dev = jax.devices()[0]
    kind = dev.device_kind
    cand_keys = {_row_key(c) for c in cands}
    base_rows, reuse = [], {}
    prev = load_artifact(path)
    if (isinstance(prev, dict) and prev.get("platform") == plat
            and prev.get("device_kind") == kind):
        for r in prev.get("rows") or []:
            if not isinstance(r, dict):
                continue
            key = _row_key(r)
            if key not in cand_keys:
                base_rows.append(r)  # other configs: accumulated cache
            elif run_match(r) and ("step_s" in r or _is_capacity_error(r)):
                reuse[key] = r

    done = []

    def snapshot(complete):
        rows = base_rows + done
        return {"metric": "attention_block_autotune", "platform": plat,
                "device": str(dev), "device_kind": kind,
                "rows": rows, "winners": _recompute_winners(rows),
                "complete": bool(complete)}

    def flush(complete):
        doc = snapshot(complete)
        write_artifact(path, doc)
        clear_cache()
        return doc

    # A certified complete doc for this platform/device kind is left
    # untouched until a candidate actually re-measures: an all-reuse
    # rerun, or one killed mid-measurement before any new row lands,
    # must not regress the committed artifact to complete:false while
    # holding the exact same data.
    certified = (isinstance(prev, dict) and prev.get("platform") == plat
                 and prev.get("device_kind") == kind
                 and prev.get("complete") is True)
    if not certified:
        flush(False)
    for cand in cands:
        key = _row_key(cand)
        if key in reuse:
            row = dict(reuse[key])
            row["reused_from_previous_run"] = True
        else:
            row = measure(cand)
            certified = False  # new data: the shipped doc no longer covers it
        done.append(row)
        log("tune: %s" % {k: v for k, v in row.items() if k != "kind"})
        if not certified:
            flush(False)
    return snapshot(True) if certified else flush(finalize)


def autotune_attention(seq_lens: Sequence[int], *, head_dim: int = 128,
                       dtype="bfloat16", causal: bool = True,
                       batch: int = 1, heads: int = 8, iters: int = 3,
                       grid: Sequence[Tuple[int, int]] = DEFAULT_GRID,
                       path: Optional[str] = None, finalize: bool = True,
                       log=print) -> dict:
    """Sweep flash (block_q, block_k) per seq_len plus one naive-XLA
    baseline row each, persisting winners + crossover verdicts into the
    tuning cache.  Returns the final artifact doc."""
    path = path or cache_path()
    dtype = _dtype_name(dtype)
    ident = {"head_dim": int(head_dim), "dtype": dtype,
             "causal": bool(causal), "batch": int(batch),
             "heads": int(heads), "iters": int(iters)}
    cands = []
    for t in seq_lens:
        for bq, bk in grid:
            cands.append(dict(kind="train_step", impl="flash",
                              seq_len=int(t), block_q=int(bq),
                              block_k=int(bk), **ident))
        cands.append(dict(kind="train_step", impl="naive_xla",
                          seq_len=int(t), block_q=0, block_k=0, **ident))

    def run_match(r):
        return (r.get("batch") == batch and r.get("heads") == heads
                and r.get("iters") == iters)

    def measure(cand):
        row = dict(cand)
        shape = (batch, heads, cand["seq_len"], head_dim)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.dtype(dtype))
                   for kk in ks)
        if cand["impl"] == "flash":
            from bigdl_tpu.ops.flash_attention import flash_attention
            # explicit blocks pin the kernel: the tuner must never be
            # rerouted by the crossover it is measuring for
            fn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=causal,
                block_q=cand["block_q"], block_k=cand["block_k"])
        else:
            from bigdl_tpu.nn.attention import dot_product_attention
            fn = lambda q, k, v: dot_product_attention(  # noqa: E731
                q, k, v, causal=causal)
        try:
            step = _train_step_time(fn, q, k, v, iters)
            row["step_s"] = round(step, 5)
            row["tokens_per_s"] = round(batch * cand["seq_len"] / step, 1)
        except Exception as e:  # noqa: BLE001 — recorded, sweep continues
            row["error"] = ("%s: %s" % (type(e).__name__, e))[:500]
        return row

    return _run_sweep(cands, measure, run_match,
                      path=path, finalize=finalize, log=log)


def autotune_paged_decode(*, slots: int = 8, heads: int = 8,
                          head_dim: int = 128, cache_len: int = 2048,
                          block_len: int = 16, dtype="bfloat16",
                          iters: int = 20, path: Optional[str] = None,
                          finalize: bool = True, log=print) -> dict:
    """Time the Pallas paged-decode kernel against the dense kc[tables]
    gather at one serving shape (full-context worst case) and persist
    the use_kernel verdict."""
    from bigdl_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    path = path or cache_path()
    dtype = _dtype_name(dtype)
    width = -(-cache_len // block_len)
    num_blocks = slots * width + 1  # + the scratch block
    ident = {"slots": int(slots), "heads": int(heads),
             "head_dim": int(head_dim), "cache_len": int(cache_len),
             "block_len": int(block_len), "dtype": dtype,
             "iters": int(iters)}
    cands = [dict(kind="paged_decode", impl="paged_kernel", **ident),
             dict(kind="paged_decode", impl="dense_gather", **ident)]

    def run_match(r):
        return r.get("iters") == iters

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (slots, heads, head_dim), jnp.dtype(dtype))
    from bigdl_tpu.serving.kvcache.blocks import pack_rows
    rows = (num_blocks, block_len, heads, head_dim)
    ka = pack_rows(jax.random.normal(ks[1], rows, jnp.dtype(dtype)))
    va = pack_rows(jax.random.normal(ks[2], rows, jnp.dtype(dtype)))
    tables = jnp.arange(1, slots * width + 1, dtype=jnp.int32).reshape(
        slots, width)
    pos = jnp.full((slots,), cache_len - 1, jnp.int32)
    fns = {
        "paged_kernel": jax.jit(lambda q, ka, va, t, p:
                                paged_decode_attention(q, ka, va, t, p)),
        "dense_gather": jax.jit(
            lambda q, ka, va, t, p:
            paged_decode_attention_reference(q, ka, va, t, p)),
    }

    def measure(cand):
        row = dict(cand)
        try:
            step = _op_step_time(fns[cand["impl"]],
                                 (q, ka, va, tables, pos), iters)
            row["step_s"] = round(step, 6)
        except Exception as e:  # noqa: BLE001
            row["error"] = ("%s: %s" % (type(e).__name__, e))[:500]
        return row

    return _run_sweep(cands, measure, run_match,
                      path=path, finalize=finalize, log=log)


#: default (m, k, n) duel shapes: decode-row (m=slots) and prefill-tile
#: (m=tokens) activations against serving-scale layer geometries
DEFAULT_QCOMPUTE_SHAPES: Tuple[Tuple[int, int, int], ...] = (
    (8, 1024, 1024), (8, 1024, 4096),
    (256, 1024, 1024), (256, 1024, 4096),
)


def autotune_qcompute(shapes: Sequence[Tuple[int, int, int]]
                      = DEFAULT_QCOMPUTE_SHAPES, *, iters: int = 20,
                      path: Optional[str] = None, finalize: bool = True,
                      log=print) -> dict:
    """The int8-compute-vs-dequant duel: per (m, k, n), time the true
    int8xint8 MXU matmul (``qmatmul_i8``: per-token activation quant +
    int32 accumulation + f32 rescale, all inside the jit) against the
    dequant-bf16 baseline (``qmatmul`` on a dequant-mode QTensor — the
    storage-only recipe).  Winners persist per device_kind in the shared
    tuning cache; ``QuantPolicy(compute="auto")`` resolves through
    :func:`lookup_qcompute`, so auto can never lose to dequant."""
    from bigdl_tpu.quant.kernels import qmatmul, qmatmul_i8
    from bigdl_tpu.quant.qtensor import quantize_array
    path = path or cache_path()
    cands = []
    for m, k, n in shapes:
        ident = {"m": int(m), "k": int(k), "n": int(n), "iters": int(iters)}
        cands.append(dict(kind="qcompute", impl="int8_compute", **ident))
        cands.append(dict(kind="qcompute", impl="dequant_bf16", **ident))

    def run_match(r):
        return r.get("iters") == iters

    fns = {"int8_compute": jax.jit(qmatmul_i8),
           "dequant_bf16": jax.jit(qmatmul)}

    def measure(cand):
        row = dict(cand)
        m, k, n = cand["m"], cand["k"], cand["n"]
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (m, k), jnp.float32)
        w = jax.random.normal(ks[1], (k, n), jnp.float32)
        qw = quantize_array(w, (0,),
                            compute="int8" if cand["impl"] == "int8_compute"
                            else "dequant")
        try:
            step = _op_step_time(fns[cand["impl"]], (x, qw), iters)
            row["step_s"] = round(step, 6)
            row["tokens_per_s"] = round(m / step, 1)
        except Exception as e:  # noqa: BLE001
            row["error"] = ("%s: %s" % (type(e).__name__, e))[:500]
        return row

    return _run_sweep(cands, measure, run_match,
                      path=path, finalize=finalize, log=log)
