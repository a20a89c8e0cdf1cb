"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the default device, no supervisor, no retries.  Drives the
two main paths through the entry points a user calls:

  serve    TransformerLM at GPT-2 XL's published widths (hidden 1600, 25
           heads, vocab 50257, context 1024) behind ``LMServingEngine``:
           warm-up, then overlapping ``submit()`` requests (greedy and
           sampled, a shared prefix), each stream replayed against offline
           ``generate()`` on the same device.
  kernels  the compiled (not interpreted) Pallas kernels — flash forward
           and backward, plain and segmented, the kernels that read a
           round's listed blocks and the routed experts' grouped matmul
           (beside ``lax.ragged_dot``: gap to float64, time alone) —
           against the XLA references in the repo.
  train    ResNet-50, ImageNet shapes, bf16 compute / f32 master, NHWC,
           through ``Optimizer.create(...).optimize()``.
  --chips 4   ONLY the multi-chip phase: ``DistriOptimizer`` on a 4-device
           data mesh against the same steps on one device.

Every line but the last is one JSON object per phase (what ran, shapes,
dtype, depth, compile seconds, cache hits, peak device bytes, which
attention implementation each call resolved to).  The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exit is non-zero, with no such line, when JAX finds no TPU, when a phase
raises, when a Pallas call on the path ran interpreted, or when a
comparison misses.  Timings printed here are set-up facts, not results.

CPU rehearsal: the phase functions take a ``Sizes``; tests/test_chip_smoke.py
calls them at toy size with ``require_compiled=False``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# everything below is the program under test: in a directory that holds
# this script and nothing else, the import fails and so does the smoke
from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, MiniBatch
from bigdl_tpu.models import ResNet
from bigdl_tpu.models.transformer import TransformerLM, window_mask
from bigdl_tpu.models.transformer.generate import _paged_attention, generate
from bigdl_tpu.nn.attention import dot_product_attention, segment_mask
from bigdl_tpu.ops.flash_attention import flash_attention, use_flash_auto
from bigdl_tpu.ops.grouped_attention import grouped_decode_attention
from bigdl_tpu.ops.grouped_matmul import grouped_matmul
from bigdl_tpu.ops.latent_attention import latent_decode_attention
from bigdl_tpu.serving.kvcache.blocks import (SCRATCH_BLOCK, live_list,
                                              row_width)
from bigdl_tpu.optim import SGD, Optimizer, Trigger
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.utils.engine import configure_compile_cache

#: stated tolerances ------------------------------------------------------
#: kernel vs XLA reference: max |a-b| / max |b|.  Both sides multiply in
#: bf16 passes on the MXU (TPU default precision), in different orders.
KERNEL_TOL = 5e-2
#: a diverging stream passes only if the two tokens' scores at the first
#: differing position are this close (seeded weights give near-flat
#: logits, and two batch widths need not reduce in the same order)
NEAR_TIE_TOL = 2e-2
#: 4-chip loss vs the same steps on one device (bf16 weight all-gather and
#: gradient reduce-scatter round differently on 1 and 4 devices)
MULTICHIP_LOSS_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a phase runs at.  ``REAL`` is what the chip runs; tests build a
    toy one.  Widths are the published ones; only depth may be cut."""
    # LM (GPT-2 XL)
    vocab: int = 50257
    hidden: int = 1600
    heads: int = 25
    layers: int = 48
    context: int = 1024
    slots: int = 4
    block_len: int = 16
    num_blocks: int = 64
    prefill_buckets: Tuple[int, ...] = (32, 128)
    prompt_lens: Tuple[int, ...] = (24, 100, 24, 100)
    shared_prefix: int = 64         # tokens two requests have in common
    shared_tail: int = 8
    max_new: int = 12
    # kernels: (B, H, T, D, dtype) flash cases
    flash_cases: Tuple[tuple, ...] = (
        (2, 25, 1024, 64, "bfloat16"),
        (2, 25, 1024, 64, "float32"),
        (1, 8, 4096, 128, "bfloat16"),
    )
    flash_block: int = 128
    # the kernels that read a round's listed blocks where they lie, at their
    # cells' rows: (kind, S, H, H_kv, D, blk, M, window, dtype) -- Solar's
    # softmax layer, Laguna's sliding layers, Ling's latent row (D its lanes,
    # a third of them the score's width, all but a ninth values)
    listed_cases: Tuple[tuple, ...] = (
        ("grouped", 16, 64, 8, 128, 16, 128, None, "bfloat16"),
        ("grouped", 8, 72, 8, 128, 16, 160, 512, "bfloat16"),
        ("latent", 8, 32, 1, 576, 16, 256, None, "bfloat16"),
    )
    # the routed experts' grouped matmul at the four cells' rows and hit
    # patterns: (rows, real rows, E, D, F, experts hit, dtype) -- GLM's verify
    # round, Solar's, Ling's and Laguna's decode rounds
    expert_cases: Tuple[tuple, ...] = (
        (512, 512, 64, 2048, 1536, 63, "bfloat16"),
        (1024, 128, 40, 4096, 1280, 19, "bfloat16"),
        (256, 32, 64, 2560, 768, 11, "bfloat16"),
        (320, 160, 128, 3072, 1024, 8, "bfloat16"),
    )
    # ResNet-50 training
    resnet_depth: int = 50
    resnet_dataset: str = "imagenet"
    image: int = 224
    classes: int = 1000
    #: step A's decision (tests/test_chip_compile.py, CHANGES.md PR 21):
    #: 512 compiles for v5e at 14.29 GiB of one program's arguments +
    #: temporaries, 256 at 8.81 GiB.  The smoke keeps other phases'
    #: leftovers and the data set's batches in the same process, which
    #: memory_analysis() does not count, so it trains at 256.
    train_batch: int = 256
    train_iters: int = 6
    multichip_batch: int = 256      # global; 64 per chip on four
    multichip_iters: int = 4


    @property
    def offline_cache_len(self) -> int:
        """One cache length for every offline ``generate()`` call, so its
        decode scan compiles once."""
        return max(self.prompt_lens + (
            self.shared_prefix + self.shared_tail,)) + self.max_new


REAL = Sizes()


# --------------------------------------------------------------------------
# instrumentation: what compiled, what hit the cache, what ran interpreted
# --------------------------------------------------------------------------

class Probe:
    """Counts backend compiles and persistent-cache hits/misses through
    jax.monitoring, and records the ``interpret`` flag of every
    ``pallas_call`` traced while the smoke runs."""

    def __init__(self):
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.pallas_calls: list = []    # (kernel name, interpreted?)
        self._installed = False

    def install(self) -> "Probe":
        if self._installed:
            return self
        self._installed = True
        from jax import monitoring
        from jax.experimental import pallas as pl

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

        real_call = pl.pallas_call

        def recording_pallas_call(kernel, *args, **kw):
            name = kw.get("name") or getattr(
                getattr(kernel, "func", kernel), "__name__", "kernel")
            self.pallas_calls.append((name, bool(kw.get("interpret"))))
            return real_call(kernel, *args, **kw)

        pl.pallas_call = recording_pallas_call
        return self

    def mark(self) -> dict:
        return {"compiles": self.compiles,
                "compile_seconds": self.compile_seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "pallas": len(self.pallas_calls)}

    def since(self, mark: dict) -> dict:
        calls = self.pallas_calls[mark["pallas"]:]
        return {
            "backend_compiles": self.compiles - mark["compiles"],
            "compile_seconds": round(
                self.compile_seconds - mark["compile_seconds"], 2),
            "persistent_cache_hits": self.cache_hits - mark["cache_hits"],
            "persistent_cache_misses":
                self.cache_misses - mark["cache_misses"],
            "pallas_calls_traced": len(calls),
            "pallas_interpreted": sorted({n for n, i in calls if i}),
        }


PROBE = Probe()


class SmokeFailure(AssertionError):
    """A comparison missed; the message says which."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _free_device_memory() -> None:
    """Between phases: collect what the caller has dropped (a model goes
    with its last reference — no cache of the program keeps one) and
    unload the compiled programs of the phase that ended."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1e-6, float(np.max(np.abs(b)))))


# --------------------------------------------------------------------------
# phase: serve
# --------------------------------------------------------------------------

def _build_lm(sz: Sizes, seed: int) -> TransformerLM:
    return TransformerLM(
        vocab_size=sz.vocab, hidden_size=sz.hidden, n_head=sz.heads,
        n_layers=sz.layers, max_len=sz.context).build(seed=seed).evaluate()


def _requests(sz: Sizes, seed: int) -> list:
    """Mixed prompt lengths, greedy and sampled, two sharing a prefix."""
    rs = np.random.RandomState(seed)
    reqs = []
    for i, t in enumerate(sz.prompt_lens):
        reqs.append({"prompt": rs.randint(1, sz.vocab + 1, size=t),
                     "temperature": 0.0 if i % 2 == 0 else 0.8,
                     "seed": 100 + i})
    shared = rs.randint(1, sz.vocab + 1, size=sz.shared_prefix)
    for i in range(2):
        tail = rs.randint(1, sz.vocab + 1, size=sz.shared_tail)
        reqs.append({"prompt": np.concatenate([shared, tail]),
                     "temperature": 0.0, "seed": 200 + i})
    return reqs


def _offline(model, req: dict, sz: Sizes) -> np.ndarray:
    """The replay oracle: offline generate() on the same device."""
    out = generate(model, model.params,
                   req["prompt"][None].astype(np.int32), sz.max_new,
                   temperature=req["temperature"],
                   rng=jax.random.PRNGKey(req["seed"]),
                   cache_len=sz.offline_cache_len)
    return np.asarray(out)[0, len(req["prompt"]):]


def _near_tie(model, req: dict, agreed: Sequence[int], tok_a: int,
              tok_b: int, sz: Sizes) -> float:
    """Score gap between two candidate tokens at the first position where
    two streams differ, from a third evaluation (the training forward on
    prompt + agreed tokens).  Greedy: the gap in logits.  Sampled: the gap
    after the position's own Gumbel draw (``categorical`` is an argmax of
    logits / temperature + Gumbel noise), so a flipped draw is judged on
    what was actually compared.  Tokens are 1-based."""
    ids = np.concatenate([req["prompt"], np.asarray(agreed, np.int64)])
    logp = model.f(model.params, ids[None].astype(np.int32))[0, -1]
    scores = np.asarray(logp, np.float32)
    temp = float(req["temperature"])
    if temp > 0.0:
        # the key chain submit() and generate() both use
        rng, first = jax.random.split(jax.random.PRNGKey(req["seed"]))
        i = len(agreed)
        key = first if i == 0 else jax.random.split(rng, sz.max_new - 1)[i - 1]
        noise = jax.random.gumbel(key, (1, scores.shape[0]), jnp.float32)[0]
        scores = scores / temp + np.asarray(noise)
    top = float(scores.max())
    gap = abs(float(scores[tok_a - 1]) - float(scores[tok_b - 1]))
    # both must also be the contenders for the argmax, not two also-rans
    return max(gap, top - float(scores[tok_a - 1]),
               top - float(scores[tok_b - 1]))


def _compare_stream(model, req: dict, got: np.ndarray, want: np.ndarray,
                    sz: Sizes) -> dict:
    """Token-for-token, else a near-tie at the first difference."""
    _check(len(got) == len(want) == sz.max_new,
           f"stream length {len(got)} vs offline {len(want)}")
    diff = np.nonzero(got != want)[0]
    if diff.size == 0:
        return {"exact": True}
    i = int(diff[0])
    gap = _near_tie(model, req, got[:i], int(got[i]), int(want[i]), sz)
    _check(gap <= NEAR_TIE_TOL,
           f"stream diverges from offline generate() at token {i} "
           f"({int(got[i])} vs {int(want[i])}) with score gap {gap:.3g} "
           f"> {NEAR_TIE_TOL}")
    return {"exact": False, "first_difference": i,
            "tokens": [int(got[i]), int(want[i])], "score_gap": gap}


def _engine_compiles(eng: LMServingEngine) -> dict:
    return {"prefill": eng.prefill_cache.stats()["entries"],
            "prefix_prefill": eng.prefix_prefill_cache.stats()["entries"],
            "insert": len(eng._insert_execs),
            "decode": int(eng._decode_exec is not None)}


def _serve_requests(eng: LMServingEngine, reqs: list, sz: Sizes) -> list:
    """The first shared-prefix request alone (its chain enters the radix
    cache), then everything else at once: more requests than slots, so
    continuous batching queues one, and the second shared-prefix request
    must hit the cache."""
    def submit(r):
        return eng.submit(r["prompt"], max_new_tokens=sz.max_new,
                          temperature=r["temperature"], rng=r["seed"])

    first = len(reqs) - 2
    streams = {first: submit(reqs[first])}
    streams[first].result(timeout=600)
    for i, r in enumerate(reqs):
        if i != first:
            streams[i] = submit(r)
    return [np.asarray(streams[i].result(timeout=600))[len(r["prompt"]):]
            for i, r in enumerate(reqs)]


def _run_engine(model, reqs, sz: Sizes) -> Tuple[list, dict]:
    eng = LMServingEngine(
        model, slots=sz.slots, cache_len=sz.context,
        max_new_tokens=sz.max_new, prefill_buckets=sz.prefill_buckets,
        block_len=sz.block_len, num_blocks=sz.num_blocks)
    try:
        t0 = time.perf_counter()
        eng.warmup()
        eng.warmup_prefix(
            suffix_lens=[sz.shared_tail],
            prefix_blocks=[sz.shared_prefix // sz.block_len])
        warm_s = time.perf_counter() - t0
        warm = _engine_compiles(eng)
        outs = _serve_requests(eng, reqs, sz)
        after = _engine_compiles(eng)
        stats = eng.stats()
        prefix = (stats["kvcache"]["prefix_cache"] or {})
        info = {
            "decode_attn_resolved": eng.decode_attn,
            "kv_dtype": str(jnp.dtype(eng._cache_dtype)),
            "warmup_seconds": round(warm_s, 2),
            "executables_after_warmup": warm,
            "compiles_after_warmup": {
                k: after[k] - warm[k] for k in warm},
            "compile_cache_misses_in_traffic":
                stats["prefill_cache"]["misses"]
                + stats["prefix_prefill_cache"]["misses"],
            "compile_cache_hits_in_traffic":
                stats["prefill_cache"]["hits"]
                + stats["prefix_prefill_cache"]["hits"],
            "prefix_cache": {k: prefix.get(k) for k in
                             ("lookups", "hits", "prefill_tokens_saved")},
            "kv_blocks": sz.num_blocks,
        }
        _check((prefix.get("hits") or 0) >= 1,
               f"no request hit the prefix cache: {prefix}")
        _check(all(v == 0 for v in info["compiles_after_warmup"].values())
               and info["compile_cache_misses_in_traffic"] == 0,
               f"engine compiled after warm-up: "
               f"{info['compiles_after_warmup']}, CompileCache misses "
               f"{info['compile_cache_misses_in_traffic']}")
        return outs, info
    finally:
        eng.close()


def _prefill_attention(sz: Sizes) -> dict:
    """Which attention implementation each prefill bucket resolves to
    (``attention_impl="auto"``: flash from FLASH_AUTO_MIN_T up on TPU)."""
    return {str(b): "flash" if use_flash_auto(b) else "xla"
            for b in sz.prefill_buckets}


def phase_serve(sz: Sizes = REAL, seed: int = 0) -> Tuple[dict, object]:
    """Returns the printed row and the model (whose memory must come back
    when the caller lets go of it: tests/test_chip_smoke.py)."""
    mark = PROBE.mark()
    t0 = time.perf_counter()
    model = _build_lm(sz, seed)
    build_s = time.perf_counter() - t0
    reqs = _requests(sz, seed)
    outs, info = _run_engine(model, reqs, sz)
    _free_device_memory()       # the closed engine's arenas
    t0 = time.perf_counter()
    refs = [_offline(model, r, sz) for r in reqs]
    offline_s = time.perf_counter() - t0
    streams = [_compare_stream(model, r, o, w, sz)
               for r, o, w in zip(reqs, outs, refs)]
    row = {
        "phase": "serve", "ok": True,
        "model": "TransformerLM at GPT-2 XL widths",
        "hidden": sz.hidden, "heads": sz.heads, "vocab": sz.vocab,
        "context": sz.context, "layers": sz.layers,
        "depth_cut": sz.layers != 48,
        "dtype": str(jnp.dtype(model.params["embed"].dtype)),
        "dtype_note": "f32 weights and KV: the engine has no dtype "
                      "argument, arenas take the embedding's dtype",
        "gradient_buffers_allocated": model.grad_params is not None,
        "slots": sz.slots, "prefill_buckets": list(sz.prefill_buckets),
        "prompt_lens": [len(r["prompt"]) for r in reqs],
        "temperatures": [r["temperature"] for r in reqs],
        "max_new": sz.max_new,
        "prefill_attention": _prefill_attention(sz),
        "build_seconds": round(build_s, 2),
        "offline_generate_seconds": round(offline_s, 2),
        "streams": streams,
        "streams_exact": sum(s["exact"] for s in streams),
        "near_tie_tolerance": NEAR_TIE_TOL,
        **info, **PROBE.since(mark), "peak_bytes": _peak_bytes(),
    }
    _emit(row)
    return row, model


# --------------------------------------------------------------------------
# phase: kernels
# --------------------------------------------------------------------------

def _flash_case(case: tuple, segmented: bool, block: int, seed: int) -> dict:
    b, h, t, d, dtname = case
    dt = jnp.dtype(dtname)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, (b, h, t, d), jnp.float32).astype(dt)
                  for kk in ks)
    seg = None
    if segmented:
        # three documents of unequal length packed into the window
        cuts = jnp.asarray([t // 5, t // 2])
        seg = jnp.broadcast_to(
            jnp.sum(jnp.arange(t)[None, :] >= cuts[:, None], axis=0),
            (b, t)).astype(jnp.int32)
    scale = 1.0 / float(np.sqrt(d))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, scale=scale, segment_ids=seg,
        block_q=block, block_k=block)
    mask = None if seg is None else segment_mask(seg, seg)
    ref = lambda q, k, v: dot_product_attention(  # noqa: E731
        q, k, v, causal=True, mask=mask, scale=scale)
    o = jax.jit(flash)(q, k, v)
    o_ref = jax.jit(ref)(q, k, v)
    g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    errs = {"o": _rel_err(o, o_ref)}
    errs.update({n: _rel_err(a, r)
                 for n, a, r in zip(("dq", "dk", "dv"), g, g_ref)})
    _check(bool(np.isfinite(np.asarray(o, np.float32)).all()),
           f"flash {case} produced non-finite values")
    _check(max(errs.values()) <= KERNEL_TOL,
           f"flash {case} segmented={segmented} vs XLA reference: {errs} "
           f"> {KERNEL_TOL}")
    return {"kernel": "flash fwd+bwd", "shape": [b, h, t, d],
            "dtype": dtname, "segmented": segmented, "block": block,
            "rel_err": {k: round(e, 5) for k, e in errs.items()}}


#: a kernel that multiplies exact products may lie this far from float64
#: where its oracle lies nearer (the order of the float32 sums)
LISTED_TOL = 1e-5


def _float64_attention(q, k, v, tables, lengths, n_kv, window, score_dim):
    """A float64 softmax a slot and query head over the chain's own rows: ``q``
    (S, H, D), ``k`` / ``v`` one layer's arena (N, blk, lanes), query head i on
    the lanes of K/V head ``i // (H / n_kv)`` -> (S, H, D)."""
    s_, h, d = q.shape
    g = h // n_kv
    out = np.zeros((s_, h, d))
    for s, length in enumerate(lengths):
        if not length:
            continue
        lo = 0 if window is None else max(length - window, 0)
        rows = [a[tables[s]].reshape(-1, a.shape[-1])[lo:length].astype(
            np.float64) for a in (k, v)]
        for i in range(h):
            lanes = slice((i // g) * d, (i // g + 1) * d)
            score = rows[0][:, lanes] @ q[s, i] / np.sqrt(score_dim)
            e = np.exp(score - score.max())
            out[s, i] = (e / e.sum()) @ rows[1][:, lanes]
    return out


def _listed_case(case: tuple, seed: int) -> dict:
    """A kernel that reads a round's listed blocks in place and the XLA walk
    it replaces on the chip, each against float64 on the same arenas: what
    no CPU test can see is a piece of a product dropped by the TPU compiler
    (PERF.md, PR 36)."""
    kind, s, h, n_kv, d, blk, m, window, dtname = case
    dt, latent = jnp.dtype(dtname), kind == "latent"
    rs = np.random.RandomState(seed)
    n, w = s * m + 1, row_width(n_kv, d)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lanes = jnp.arange(w) < n_kv * d                        # the lane padding
    arenas = tuple(
        jnp.where(lanes, jax.random.normal(kk, (1, n, blk, w), jnp.float32),
                  0).astype(dt) for kk in keys[:1 if latent else 2])
    q = 2 * jax.random.normal(keys[2], (s, h, 1, d), jnp.float32)
    new = [jax.random.normal(kk, (s, n_kv, 1, d), jnp.float32).astype(dt)
           for kk in keys[3:4 if latent else 5]]
    # ragged chains of scattered blocks: an idle slot, a full table, one block
    lengths = rs.randint(1, m * blk + 1, size=s)
    lengths[:3] = (0, m * blk, 1)
    order = 1 + rs.permutation(n - 1)
    tables = np.full((s, m), SCRATCH_BLOCK, np.int32)
    chains = []
    for i, length in enumerate(lengths):
        held = -(-int(length) // blk)
        if held:
            tables[i, :held] = order[i * m:i * m + held]
            chains.append((i, tables[i, :held]))
    live = jnp.asarray(live_list(chains, s * m, s))
    pos = jnp.asarray(np.maximum(lengths - 1, 0), jnp.int32)
    # the block a slot's new row lands in (an idle slot's: the scratch block)
    at = jnp.asarray(tables[np.arange(s), np.maximum(lengths - 1, 0) // blk])
    score_dim = d // 3 if latent else d
    values = d - d // 9 if latent else d

    def walk(q, new, arenas):
        _, owner, where = live
        k_pos = where[:, None] * blk + jnp.arange(blk)[None, :]
        mask = (window_mask(pos[jnp.minimum(owner, s - 1)][:, None], k_pos,
                            window) & (owner < s)[:, None, None])
        return _paged_attention(q, new[0], None if latent else new[1], arenas,
                                0, at[:, None], (pos % blk)[:, None], live,
                                mask,
                                score_dim=score_dim)

    def kernel(q, arenas):
        table, lens = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
        if latent:
            return latent_decode_attention(q, arenas[0], table, lens,
                                           score_dim=score_dim, layer=0,
                                           value_lanes=values)
        return grouped_decode_attention(q, *arenas, table, lens, layer=0,
                                        n_kv_head=n_kv, window=window)

    walked, arenas = jax.jit(walk)(q, new, arenas)
    got = jax.jit(kernel)(q, arenas)
    rows = [np.asarray(a[0].astype(jnp.float32)) for a in arenas]
    want = _float64_attention(
        np.asarray(q, np.float64)[:, :, 0], rows[0], rows[-1], tables, lengths,
        n_kv, window, score_dim)[..., :values]
    gap = {name: float(np.max(np.abs(np.asarray(o, np.float64)[:, :, 0, :values]
                                     - want)) / np.max(np.abs(want)))
           for name, o in (("kernel", got), ("walk", walked))}
    _check(bool(np.isfinite(np.asarray(got)).all()),
           f"{kind} decode {case} produced non-finite values")
    _check(not np.asarray(got)[lengths == 0].any(),
           f"{kind} decode {case}: an idle slot's rows are not zeros")
    _check(gap["kernel"] <= max(gap["walk"], LISTED_TOL),
           f"{kind} decode {case} vs float64: the kernel {gap['kernel']:.3g}, "
           f"the walk {gap['walk']:.3g}")
    return {"kernel": f"{kind} decode", "slots": s, "heads": h, "kv_heads": n_kv,
            "head_dim": d, "block_len": blk, "table_width": m, "window": window,
            "dtype": dtname, "positions": int(lengths.sum()),
            "gap_to_float64": {k: float(f"{v:.3g}") for k, v in gap.items()}}


def _expert_case(case: tuple, seed: int, reps: int = 20) -> dict:
    """One product of a routed layer through ``ops.grouped_matmul`` and through
    ``lax.ragged_dot``, each against a float64 loop over the hit experts on
    the same operands, and -- compiled, on the chip -- the time of each alone:
    ``reps`` calls in one program, each reading the one before."""
    rows, real, e, d, f, hit, dtname = case
    dt = jnp.dtype(dtname)
    rs = np.random.RandomState(seed)
    sizes = np.zeros(e, np.int64)
    sizes[rs.permutation(e)[:hit]] = 1 + rs.multinomial(
        real - hit, np.ones(hit) / hit)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (rows, d), jnp.float32).astype(dt)
    w = (jax.random.normal(keys[1], (e, d, f), jnp.float32)
         / np.sqrt(d)).astype(dt)
    s = jnp.asarray(sizes, jnp.int32)
    paths = {
        "kernel": lambda a: grouped_matmul(a, w, s),
        "ragged_dot": lambda a: jax.lax.ragged_dot(
            a, w, s, preferred_element_type=jnp.float32).astype(dt)}
    want, lo = np.zeros((real, f)), 0
    for i in np.flatnonzero(sizes):
        n = int(sizes[i])
        want[lo:lo + n] = (np.asarray(x[lo:lo + n], np.float64)
                           @ np.asarray(w[i], np.float64))
        lo += n
    outs = {name: np.asarray(jax.jit(fn)(x), np.float64)
            for name, fn in paths.items()}
    gap = {name: float(np.max(np.abs(o[:real] - want)) / np.max(np.abs(want)))
           for name, o in outs.items()}
    _check(not outs["kernel"][real:].any(),
           f"grouped matmul {case}: rows no expert owns are not zeros")
    # a product is rounded to the rows' dtype: half a unit in its last place
    _check(gap["kernel"] <= max(gap["ragged_dot"], 2 * float(jnp.finfo(dt).eps)),
           f"grouped matmul {case} vs float64: the kernel {gap['kernel']:.3g}, "
           f"ragged_dot {gap['ragged_dot']:.3g}")
    row = {"kernel": "grouped matmul", "rows": rows, "real_rows": real,
           "experts": e, "d_model": d, "width": f, "experts_hit": hit,
           "dtype": dtname, "hit_bytes": int(hit * d * f * dt.itemsize),
           "gap_to_float64": {k: float(f"{v:.3g}") for k, v in gap.items()}}
    if jax.default_backend() == "tpu":      # a time is a chip's or nothing
        row["alone_ms"] = {}
        for name, fn in paths.items():
            loop = jax.jit(lambda a, fn=fn: jax.lax.fori_loop(
                0, reps, lambda _, b: b + (fn(b)[:, :1] * 0).astype(dt), a))
            loop(x).block_until_ready()
            t0 = time.perf_counter()
            loop(x).block_until_ready()
            row["alone_ms"][name] = float(
                f"{(time.perf_counter() - t0) / reps * 1e3:.4g}")
    return row


def phase_kernels(sz: Sizes = REAL, seed: int = 0,
                  require_compiled: bool = True) -> dict:
    mark = PROBE.mark()
    cases = []
    for case in sz.flash_cases:
        for segmented in (False, True):
            cases.append(_flash_case(case, segmented, sz.flash_block, seed))
    for case in sz.listed_cases:
        cases.append(_listed_case(case, seed))
        _free_device_memory()
    for case in sz.expert_cases:
        cases.append(_expert_case(case, seed))
        _free_device_memory()
    probe = PROBE.since(mark)
    if require_compiled:
        _check(probe["pallas_calls_traced"] > 0,
               "no Pallas call was traced in the kernels phase")
    row = {"phase": "kernels", "ok": True, "tolerance": KERNEL_TOL,
           "cases": cases, **probe, "peak_bytes": _peak_bytes()}
    _emit(row)
    return row


# --------------------------------------------------------------------------
# phase: train
# --------------------------------------------------------------------------

class _LossLog:
    """A plain train summary: the optimizers write Loss every step."""

    def __init__(self):
        self.losses: list = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(float(value))


def _resnet(sz: Sizes, seed: int):
    return ResNet(class_num=sz.classes, depth=sz.resnet_depth,
                  dataset=sz.resnet_dataset,
                  data_format="NHWC").build(seed=seed)


def _image_batch(sz: Sizes, batch: int, seed: int) -> MiniBatch:
    import ml_dtypes
    rs = np.random.RandomState(seed)
    x = rs.standard_normal((batch, sz.image, sz.image, 3)).astype(
        np.float32).astype(ml_dtypes.bfloat16)
    y = rs.randint(1, sz.classes + 1, size=batch).astype(np.float32)
    return MiniBatch(x, y)


def phase_train(sz: Sizes = REAL, seed: int = 0) -> dict:
    mark = PROBE.mark()
    model = _resnet(sz, seed)
    data = DataSet.array([_image_batch(sz, sz.train_batch, seed)])
    log = _LossLog()
    opt = Optimizer.create(model, data, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learning_rate=0.02, momentum=0.9,
                             dampening=0.0))
    opt.set_compute_dtype(jnp.bfloat16)
    opt.set_train_summary(log)
    opt.set_end_when(Trigger.max_iteration(sz.train_iters))
    t0 = time.perf_counter()
    opt.optimize()
    wall = time.perf_counter() - t0
    losses = log.losses
    _check(len(losses) == sz.train_iters,
           f"{len(losses)} losses for {sz.train_iters} iterations")
    _check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall: {losses[0]} -> {losses[-1]}")
    step_programs = opt._step_fn._cache_size()
    _check(step_programs == 1,
           f"the step program compiled {step_programs} times")
    row = {"phase": "train", "ok": True,
           "model": f"ResNet-{sz.resnet_depth} ({sz.resnet_dataset} shapes)",
           "optimizer": type(opt).__name__,
           "image": [sz.image, sz.image, 3], "layout": "NHWC",
           "compute_dtype": "bfloat16", "master_dtype": "float32",
           "batch": sz.train_batch, "iterations": sz.train_iters,
           "losses": [round(x, 4) for x in losses],
           "step_programs": step_programs,
           "wall_seconds_with_compile": round(wall, 2),
           **PROBE.since(mark), "peak_bytes": _peak_bytes()}
    _emit(row)
    return row


# --------------------------------------------------------------------------
# phase: four chips (behind --chips 4; the driver never runs it)
# --------------------------------------------------------------------------

def _shard_devices(aval) -> list:
    """[(device id, index)] of one step operand, from the sharding the
    optimizer recorded off the real array."""
    sh = aval.sharding
    return sorted(
        (int(d.id), str(idx))
        for d, idx in sh.addressable_devices_indices_map(aval.shape).items())


def _distri_run(sz: Sizes, seed: int, devices, accum: int):
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.mesh import DATA_AXIS, create_mesh
    mesh = create_mesh({DATA_AXIS: len(devices)}, devices=devices)
    model = _resnet(sz, seed)
    data = DataSet.array([_image_batch(sz, sz.multichip_batch, seed)],
                         distributed=True)
    log = _LossLog()
    opt = DistriOptimizer(model, data, nn.ClassNLLCriterion(), mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.02, momentum=0.9,
                             dampening=0.0))
    opt.set_compute_dtype(jnp.bfloat16)
    if accum > 1:
        opt.set_gradient_accumulation(accum)
    opt.set_train_summary(log)
    opt.set_end_when(Trigger.max_iteration(sz.multichip_iters))
    opt.optimize()
    return opt, mesh, log.losses


def phase_multichip(sz: Sizes = REAL, seed: int = 0, devices=None,
                    require_platform: Optional[str] = "tpu") -> dict:
    mark = PROBE.mark()
    devices = list(jax.devices() if devices is None else devices)
    _check(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    _check(len({d.id for d in devices}) == 4, "devices are not distinct")
    if require_platform:
        _check(all(d.platform == require_platform for d in devices),
               f"not all {require_platform}: "
               f"{[d.platform for d in devices]}")
    opt4, mesh4, losses4 = _distri_run(sz, seed, devices, accum=1)
    w_aval, opt_avals, _, data_aval, labels_aval = opt4._step_avals[:5]
    shards = {
        "mesh_devices": [int(d.id) for d in mesh4.devices.flat],
        "parameter_shards": _shard_devices(w_aval),
        "optimizer_shards": {
            k: _shard_devices(v) for k, v in opt_avals.items()
            if getattr(v, "ndim", 0) >= 1},
        "batch_shards": _shard_devices(data_aval),
        "label_shards": _shard_devices(labels_aval),
    }
    for name in ("parameter_shards", "batch_shards", "label_shards"):
        _check(len({d for d, _ in shards[name]}) == 4
               and len({i for _, i in shards[name]}) == 4,
               f"{name} do not sit one per device: {shards[name]}")
    # the program as written (lowered) must hold the ZeRO-1 pair.  What
    # the TPU compiler keeps of it is printed, not required: on a v5e 2x2
    # it spells both collectives as whole-vector all-reduces (about twice
    # the bytes), so the issue's criterion "the compiled program shows the
    # reduce-scatter/all-gather" is NOT met — an open defect (ROADMAP S9),
    # stated in the row and never passed off by a byte count
    from bigdl_tpu.utils import profiling
    lowered = profiling.collective_footprint(
        opt4._step_fn_ref.lower(*opt4._step_avals).as_text(dialect="hlo"))
    footprint = opt4.collective_footprint()
    _check(lowered.get("all-gather", 0) > 0
           and lowered.get("reduce-scatter", 0) > 0,
           f"no all-gather/reduce-scatter in the lowered step: {lowered}")
    _check(sum(footprint.values()) > 0,
           f"the compiled 4-device step holds no collective: {footprint}")
    zero1_compiled = (footprint.get("all-gather", 0) > 0
                      and footprint.get("reduce-scatter", 0) > 0)
    # what it is compared with: the same steps on ONE device.  Four
    # micro-batches there see the rows (and the BatchNorm statistics) the
    # four devices saw here, so the losses are the same arithmetic.
    del opt4
    _free_device_memory()
    _, _, losses1 = _distri_run(sz, seed, devices[:1], accum=4)
    rel = [abs(a - b) / max(1e-6, abs(b)) for a, b in zip(losses4, losses1)]
    _check(bool(np.isfinite(losses4).all()) and len(losses4) == len(losses1),
           f"bad losses: {losses4} vs {losses1}")
    _check(max(rel) <= MULTICHIP_LOSS_RTOL,
           f"4-device losses {losses4} vs 1-device {losses1}: "
           f"rel {rel} > {MULTICHIP_LOSS_RTOL}")
    row = {"phase": "multichip", "ok": True,
           "model": f"ResNet-{sz.resnet_depth} ({sz.resnet_dataset} shapes)",
           "optimizer": "DistriOptimizer", "global_batch": sz.multichip_batch,
           "per_device_batch": sz.multichip_batch // 4,
           "iterations": sz.multichip_iters,
           "losses_4_devices": [round(x, 4) for x in losses4],
           "losses_1_device": [round(x, 4) for x in losses1],
           "loss_rel_diff": [round(x, 5) for x in rel],
           "loss_rtol": MULTICHIP_LOSS_RTOL,
           "collective_footprint_bytes_lowered": lowered,
           "collective_footprint_bytes_compiled": footprint,
           "zero1_collectives_in_compiled": zero1_compiled, **shards,
           **PROBE.since(mark), "peak_bytes": _peak_bytes()}
    _emit(row)
    return row


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def _preflight(chips: int) -> dict:
    """No chip, no smoke: everything below must hold before any phase."""
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {d0.platform!r}); "
            f"this script proves the chip path and does not run without one")
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX reports {len(devices)} "
            f"devices")
    from bigdl_tpu import native
    from bigdl_tpu.serving.placement.topology import DeviceTopology
    from bigdl_tpu.utils.profiling import device_peaks
    topo = DeviceTopology.detect()
    _check(not topo.degraded and topo.n_devices == chips
           and topo.platform == "tpu",
           f"degraded or non-TPU topology: {topo.describe()}")
    peaks = device_peaks(d0.device_kind)     # raises on an unknown kind
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "peaks_source": peaks.source,
            "native_library_loaded": native.get() is not None,
            "jax": jax.__version__}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the multi-chip phase and what it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = configure_compile_cache()
    PROBE.install()
    device = _preflight(args.chips)
    _emit({"phase": "preflight", "ok": True, **device,
           "compile_cache_dir": cache_dir})
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_multichip(REAL, args.seed)
    else:
        phase_serve(REAL, args.seed)    # the LM goes with its result
        _free_device_memory()
        phase_kernels(REAL, args.seed)
        phase_train(REAL, args.seed)
    interpreted = sorted({n for n, i in PROBE.pallas_calls if i})
    _check(not interpreted,
           f"Pallas calls ran with interpret=True: {interpreted}")
    _emit({"phase": "summary", "ok": True,
           "wall_seconds": round(time.perf_counter() - t0, 1),
           "backend_compiles": PROBE.compiles,
           "persistent_cache_hits": PROBE.cache_hits,
           "persistent_cache_misses": PROBE.cache_misses,
           "pallas_calls_traced": len(PROBE.pallas_calls)})
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
