"""The drafter half of draft-verify speculation.

``DraftModel`` wraps a cheap ``TransformerLM`` (the target's int8
``quantize()`` clone by default) and runs it k tokens ahead per slot
against its OWN small dense KV arena — (L, S, H, cache_len + 1, D),
one contiguous region per slot, no paging (the drafter's cache is a
scratchpad the verifier never reads, so block sharing buys nothing).
Row ``cache_len`` is a scratch position absorbing idle-slot writes,
the dense-cache analogue of the pool's scratch block.

Device programs follow the engine's exactly-one-executable contract:
one donated AOT decode step (``_decode_step_slots`` over all S slots),
one bucketed prefill per prompt bucket through a ``CompileCache``, one
donated insert per bucket.  Drafting k tokens for however many slots
are speculating costs at most ``max(pending) + k - 1`` batched drafter
steps per round — slots that finished their chains early idle on the
scratch row, never a recompile.

State discipline: the engine emits tokens the DRAFTER hasn't attended
yet (the verify bonus token always, the k-th draft when fully
accepted).  Each slot therefore carries ``pending`` — emitted tokens
not yet fed — and every draft round starts by catching the slot up.
Rollback after a partial acceptance is the same pointer-rewind the
paged arena uses: ``q_next`` rewinds to the last valid position and
stale rows above it are overwritten before they can be attended (the
per-slot position mask in ``_decode_step_slots`` hides them until
then)."""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bigdl_tpu.serving.compile_cache import CompileCache
from bigdl_tpu.serving.spec.verify import draft_pick


def _ranked_alternates(logits_row: np.ndarray, temperature: float, key,
                       picked: int, n: int) -> List[int]:
    """The drafter's ``n`` next-best proposals from ONE logits row —
    the tree verifier's alternate branches, costing zero extra drafter
    steps.  Greedy ranks raw logits; a sampled-replay row ranks the
    chain key's Gumbel-perturbed scores (categorical IS Gumbel-argmax),
    so alternates are that draw's runner-ups.  ``picked`` (the spine
    draft) is excluded — an alternate duplicating the spine would be a
    wasted verify row."""
    z = np.asarray(logits_row, np.float64)
    if temperature > 0.0 and key is not None:
        import jax
        import jax.numpy as jnp
        t = max(temperature, 1e-6)
        g = jax.random.gumbel(jnp.asarray(key), (z.shape[0],))
        z = z / t + np.asarray(g, np.float64)
    order = np.argsort(-z, kind="stable")
    out: List[int] = []
    for tok in order:
        tok = int(tok)
        if tok == int(picked):
            continue
        out.append(tok)
        if len(out) >= n:
            break
    return out


def _ledger_record(tag: str, key: str, compiled) -> None:
    """File a directly-lowered executable's cost/memory row (best
    effort — the ledger must never break a compile path)."""
    try:
        from bigdl_tpu.obs.ledger import get_ledger
        get_ledger().record_compiled(tag, key, compiled)
    except Exception:
        pass


def _insert_slot_dense(k_cache, v_cache, k_new, v_new, slot):
    """Write a prefilled prompt's k/v (L, 1, H, Tb, D) into one slot's
    rows of the dense caches (L, S, H, C+1, D), starting at position 0.
    Bucket-padding rows land above the prompt, masked until decode
    overwrites them — the same stale-row invariant as the arenas."""
    from jax import lax
    k_cache = lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, slot, 0, 0, 0))
    v_cache = lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, slot, 0, 0, 0))
    return k_cache, v_cache


class _DraftSlot:
    __slots__ = ("q_next", "pending", "draft_base", "last_k")

    def __init__(self, prompt_len: int):
        self.q_next = prompt_len   # next drafter cache position to write
        self.pending: List[int] = []  # emitted, not yet fed (0-based)
        self.draft_base = prompt_len  # position of draft_1 last round
        self.last_k = 0            # k_eff of the last draft round


class DraftModel:
    """Runs the drafter for every speculating slot of one engine."""

    def __init__(self, model, *, slots: int, cache_len: int,
                 prefill_buckets, max_cache_entries: int = 16,
                 sampling: str = "replay", placement_tag: str = ""):
        import jax
        import jax.numpy as jnp
        from bigdl_tpu.models.transformer.generate import (
            _decode_step_slots, _prefill_parts)
        from bigdl_tpu.quant import (dequantize_entry, params_compute_tag,
                                     params_dtype_tag)

        model._built()
        self.model = model
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        if model.max_len < self.cache_len:
            raise ValueError(
                f"draft model max_len ({model.max_len}) is smaller than "
                f"the engine cache_len ({cache_len}): the drafter must "
                "cover every position the target can reach")
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in prefill_buckets)))
        self.sampling = sampling
        self._params = model.params
        self._buffers = model.buffers
        self.dtype_tag = params_dtype_tag(self._params) or "f32"
        self.compute_mode = params_compute_tag(self._params) or "f32"
        L = model.n_layers
        H, D = model._mha.n_head, model._mha.head_dim
        dt = self._params["embed"].dtype
        # scratch row at index cache_len: idle slots in a batched draft
        # step write there (garbage, masked for every real position)
        self.scratch_pos = self.cache_len
        shape = (L, self.slots, H, self.cache_len + 1, D)
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        self.steps = 0             # drafter decode steps (overhead meter)
        self.decode_compiles = 0   # exactly-one-executable witness

        def _prefill_fn(params, buffers, x):
            del buffers
            return _prefill_parts(model, dequantize_entry(params),
                                  x["ids"], x["len"] - 1)

        self.prefill_cache = CompileCache(
            _prefill_fn, max_entries=max_cache_entries,
            placement_tag=placement_tag, name="draft/prefill")

        def _decode_fn(params, token, pos, kc, vc):
            return _decode_step_slots(model, dequantize_entry(params),
                                      token, pos, kc, vc)

        self._decode_jit = jax.jit(_decode_fn, donate_argnums=(3, 4))
        self._decode_exec = None
        self._insert_jit = jax.jit(_insert_slot_dense,
                                   donate_argnums=(0, 1))
        self._insert_execs: dict = {}
        self._st: List[Optional[_DraftSlot]] = [None] * self.slots

    @property
    def arena_bytes(self) -> int:
        """HBM footprint of the drafter's dense k + v scratch arena."""
        return 2 * self.k.size * self.k.dtype.itemsize

    # -- device programs ------------------------------------------------ #
    def _decode_compiled(self):
        if self._decode_exec is None:
            import jax
            sds = jax.ShapeDtypeStruct
            tok = sds((self.slots,), np.int32)
            pos = sds((self.slots,), np.int32)
            kc = sds(self.k.shape, self.k.dtype)
            self._decode_exec = self._decode_jit.lower(
                self._params, tok, pos, kc, kc).compile()
            self.decode_compiles += 1
            _ledger_record("draft/decode", f"slots={self.slots}",
                           self._decode_exec)
        return self._decode_exec

    def _insert_compiled(self, bucket: int):
        exe = self._insert_execs.get(bucket)
        if exe is None:
            import jax
            sds = jax.ShapeDtypeStruct
            L, S, H, C1, D = self.k.shape
            cache = sds(self.k.shape, self.k.dtype)
            new = sds((L, 1, H, bucket, D), self.k.dtype)
            exe = self._insert_jit.lower(
                cache, cache, new, new,
                sds((), np.int32)).compile()
            self._insert_execs[bucket] = exe
            _ledger_record("draft/insert", f"bucket={bucket}", exe)
        return exe

    def warmup(self) -> int:
        """Compile the drafter's prefill buckets, decode step and
        inserts ahead of traffic; returns newly-compiled prefills."""
        inputs = [{"ids": np.zeros((1, b), np.int32),
                   "len": np.int32(b)} for b in self.prefill_buckets]
        n = self.prefill_cache.warmup_inputs(
            self._params, self._buffers, inputs)
        self._decode_compiled()
        for b in self.prefill_buckets:
            self._insert_compiled(b)
        return n

    # -- per-slot lifecycle --------------------------------------------- #
    def can_draft(self, prompt_len: int) -> bool:
        """Whole-prompt bucketed prefill only: the engine's chunked
        over-length admission path skips speculation rather than grow a
        second chunked prefill plane for the drafter."""
        return prompt_len <= self.prefill_buckets[-1]

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(f"prompt length {prompt_len} exceeds the "
                         f"largest draft bucket "
                         f"({self.prefill_buckets[-1]})")

    def admit(self, slot: int, prompt0: np.ndarray) -> None:
        """Prefill the drafter for one admitted request.  The drafter
        always prefills the FULL prompt (its dense cache is private, so
        there is no prefix chain to reuse)."""
        t = int(prompt0.shape[0])
        bucket = self.bucket_for(t)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :t] = prompt0
        _, k, v = self.prefill_cache(self._params, self._buffers,
                                     {"ids": ids, "len": np.int32(t)})
        self.k, self.v = self._insert_compiled(bucket)(
            self.k, self.v, k, v, np.int32(slot))
        self._st[slot] = _DraftSlot(t)

    def push(self, slot: int, token0: int) -> None:
        """Queue an emitted token the drafter hasn't attended yet."""
        self._st[slot].pending.append(int(token0))

    def release(self, slot: int) -> None:
        self._st[slot] = None

    def release_all(self) -> None:
        self._st = [None] * self.slots

    # -- the draft round ------------------------------------------------ #
    def draft_round(self, jobs: Dict[int, tuple]) -> Dict[int, tuple]:
        """Draft ``k_eff`` tokens for each job.  ``jobs`` maps slot ->
        (k_eff, temperature, keys) — or (k_eff, temperature, keys,
        alt_counts) in tree mode, where ``alt_counts[i]`` asks for that
        many ranked alternates off draft step i.  ``keys`` is an
        optional (k_eff, 2) uint32 chain-key slice.  Every job first
        catches its slot up on pending emitted tokens, then
        autoregressively drafts; all jobs advance in lockstep through
        ONE donated decode executable, with finished/absent jobs
        writing the scratch row.  Returns slot -> (drafts,
        draft_logit_rows, alternates) — logit rows kept only in
        rejection mode, where acceptance needs q; alternates is one
        ranked token list per draft step (empty unless requested)."""
        if not jobs:
            return {}
        state: Dict[int, dict] = {}
        for s, job in jobs.items():
            k_eff, temp, keys = job[:3]
            alt_counts = tuple(job[3]) if len(job) > 3 else ()
            st = self._st[s]
            feeds = list(st.pending)
            assert feeds, "draft_round on a slot with nothing pending"
            state[s] = {"feeds": feeds, "k": int(k_eff), "temp": temp,
                        "keys": keys, "drafts": [], "rows": [], "fed": 0,
                        "alts": [], "alt_counts": alt_counts,
                        "total": len(feeds) + int(k_eff) - 1}
        n_steps = max(v["total"] for v in state.values())
        keep_rows = self.sampling == "rejection"
        for _ in range(n_steps):
            token = np.zeros((self.slots,), np.int32)
            pos = np.full((self.slots,), self.scratch_pos, np.int32)
            stepped = []
            for s, v in state.items():
                if v["fed"] >= v["total"]:
                    continue
                st = self._st[s]
                nf = len(v["feeds"])
                tok = (v["feeds"][v["fed"]] if v["fed"] < nf
                       else v["drafts"][v["fed"] - nf])
                token[s] = tok
                pos[s] = st.q_next + v["fed"]
                v["fed"] += 1
                stepped.append(s)
            logits, self.k, self.v = self._decode_compiled()(
                self._params, token, pos, self.k, self.v)
            logits = np.asarray(logits)
            self.steps += 1
            for s in stepped:
                v = state[s]
                if v["fed"] >= len(v["feeds"]) and len(v["drafts"]) < v["k"]:
                    i = len(v["drafts"])
                    key = v["keys"][i] if v["keys"] is not None else None
                    v["drafts"].append(draft_pick(
                        logits[s], v["temp"], key, self.sampling))
                    if keep_rows:
                        v["rows"].append(logits[s].copy())
                    na = (v["alt_counts"][i]
                          if i < len(v["alt_counts"]) else 0)
                    v["alts"].append(_ranked_alternates(
                        logits[s], v["temp"], key, v["drafts"][-1], na)
                        if na > 0 else [])
        out = {}
        for s, v in state.items():
            st = self._st[s]
            st.draft_base = st.q_next + len(v["feeds"])
            st.q_next = st.draft_base + v["k"] - 1
            st.last_k = v["k"]
            st.pending = []
            out[s] = (v["drafts"], v["rows"] if keep_rows else None,
                      v["alts"])
        return out

    def commit(self, slot: int, accepted: int, emitted) -> None:
        """Reconcile one slot after verification: rewind ``q_next`` past
        the last VALID drafter write (drafts are only written when fed,
        so at most ``k_eff - 1`` of them are in cache) and queue the
        emitted tokens the drafter hasn't attended — always at least
        the bonus/correction token."""
        st = self._st[slot]
        valid = min(int(accepted), max(st.last_k - 1, 0))
        st.q_next = st.draft_base + valid
        st.pending = [int(t) for t in emitted[valid:]]

    # -- reading -------------------------------------------------------- #
    def describe(self) -> dict:
        return {"dtype_tag": self.dtype_tag,
                "compute_mode": self.compute_mode,
                "hidden": self.model.hidden_size,
                "layers": self.model.n_layers,
                "cache_len": self.cache_len,
                "steps": self.steps,
                "prefill_cache": self.prefill_cache.stats()}


class NgramDrafter:
    """Zero-model prompt-lookup drafter: proposals come from suffix
    n-gram matches against the request's OWN prompt + emitted tokens —
    the free-win regime for summarization / code-edit / RAG shapes
    whose outputs quote their inputs.  Duck-types the ``DraftModel``
    surface the engine drives (admit/push/commit/draft_round/release),
    with no device programs, no arena and no drafter steps: ``steps``
    and ``decode_compiles`` stay 0, which is exactly the point.

    Correctness needs nothing from the heuristic: under replay
    acceptance a proposed token is accepted IFF it equals the offline
    emission, so an unmatched (filler) node simply never accepts — KV
    written for it is garbage above the rewound pointer, same as any
    rejected draft.  Drafting is fully deterministic (pure function of
    the slot's token history), and every ingested token is validated
    against the target vocab so a corrupt client id fails loudly at
    admission instead of as an out-of-range embed gather on device."""

    def __init__(self, vocab_size: int, *, slots: int, ngram_max: int = 3,
                 max_context: int = 4096):
        self.vocab_size = int(vocab_size)
        self.slots = int(slots)
        self.ngram_max = max(1, int(ngram_max))
        # lookup window cap: suffix matching scans the whole context,
        # so bound host work per round on very long streams
        self.max_context = int(max_context)
        self._ctx: List[Optional[List[int]]] = [None] * self.slots
        self.steps = 0             # never advances: zero drafter cost
        self.decode_compiles = 0
        self.compute_mode = "ngram"
        self.dtype_tag = "none"
        self.arena_bytes = 0
        self.sampling = "replay"
        self.lookups = 0
        self.hits = 0

    # -- device-program surface (vacuous) ------------------------------- #
    def warmup(self) -> int:
        return 0

    def can_draft(self, prompt_len: int) -> bool:
        # no prefill buckets: any prompt the engine can admit is usable
        return True

    # -- per-slot lifecycle --------------------------------------------- #
    def _checked(self, toks) -> List[int]:
        out = []
        for t in np.asarray(toks, dtype=np.int64).reshape(-1).tolist():
            if not 0 <= t < self.vocab_size:
                raise ValueError(
                    f"ngram drafter fed token {t} outside the target "
                    f"vocab [0, {self.vocab_size})")
            out.append(int(t))
        return out

    def admit(self, slot: int, prompt0: np.ndarray) -> None:
        self._ctx[slot] = self._checked(prompt0)

    def push(self, slot: int, token0: int) -> None:
        self._ctx[slot].extend(self._checked([token0]))

    def commit(self, slot: int, accepted: int, emitted) -> None:
        # the drafter attends nothing, so "catching up" is just
        # extending the context with every emitted token
        del accepted
        self._ctx[slot].extend(self._checked(emitted))

    def release(self, slot: int) -> None:
        self._ctx[slot] = None

    def release_all(self) -> None:
        self._ctx = [None] * self.slots

    # -- drafting ------------------------------------------------------- #
    def _continuations(self, ctx: List[int], k: int,
                       want: int) -> List[List[int]]:
        """Ranked distinct continuations of the current suffix: longest
        matching n-gram first, most recent occurrence first — the
        prompt-lookup ranking, purely positional and deterministic."""
        out: List[List[int]] = []
        seen = set()
        L = len(ctx)
        for n in range(min(self.ngram_max, L - 1), 0, -1):
            pat = tuple(ctx[L - n:])
            for s in range(L - n - 1, -1, -1):
                if tuple(ctx[s:s + n]) == pat:
                    cont = ctx[s + n:s + n + k]
                    if cont and tuple(cont) not in seen:
                        seen.add(tuple(cont))
                        out.append(cont)
                        if len(out) >= want:
                            return out
        return out

    def draft_round(self, jobs: Dict[int, tuple]) -> Dict[int, tuple]:
        out = {}
        for s, job in jobs.items():
            k_eff = int(job[0])
            alt_counts = tuple(job[3]) if len(job) > 3 else ()
            ctx = self._ctx[s][-self.max_context:]
            self.lookups += 1
            want = 1 + (max(alt_counts) if alt_counts else 0)
            conts = self._continuations(ctx, k_eff, want)
            if conts:
                self.hits += 1
            # spine: best continuation, padded with the last context
            # token (a decent prior for degenerate/looping tails; a
            # wrong filler costs nothing under replay acceptance)
            filler = ctx[-1]
            spine = list(conts[0]) if conts else []
            spine += [filler] * (k_eff - len(spine))
            alts: List[List[int]] = []
            for i in range(k_eff):
                na = alt_counts[i] if i < len(alt_counts) else 0
                ranked: List[int] = []
                for c in conts[1:]:
                    if len(ranked) >= na:
                        break
                    if i < len(c) and c[i] != spine[i] \
                            and c[i] not in ranked:
                        ranked.append(c[i])
                alts.append(ranked)
            out[s] = (spine, None, alts)
        return out

    # -- reading -------------------------------------------------------- #
    def describe(self) -> dict:
        return {"dtype_tag": self.dtype_tag,
                "compute_mode": self.compute_mode,
                "ngram_max": self.ngram_max,
                "steps": self.steps,
                "lookups": self.lookups,
                "hit_rate": (self.hits / self.lookups
                             if self.lookups else 0.0)}


class SelfDrafter:
    """The THIRD drafter: the target's own PREDICTION MODULE
    (``TransformerLM.mtp``, DeepSeek-V3's multi-token prediction), through the
    target's OWN pool.  Where :class:`DraftModel` is a second model with a
    dense arena and prefill programs of its own and :class:`NgramDrafter` no
    model at all, this one is one more block of the model being served: its
    block's latent rows are one more arena layer of the target's
    ``BlockPool(latent=True)``, filled by the target's prefills (a radix hit
    shares them with the prefix), read through the same block tables, and its
    pairs run INSIDE the round's one step program
    (``generate._selfdraft_step_paged``: verify, pick, draft), so nothing here
    holds device state: this object is the drafter's description and its
    books.  One draft a slot and round (``k`` = 1: one module)."""

    def __init__(self, model, *, arena_layer: int):
        from bigdl_tpu.quant import params_compute_tag, params_dtype_tag
        self.model = model
        self.arena_layer = int(arena_layer)
        self.dtype_tag = params_dtype_tag(model.params) or "f32"
        self.compute_mode = params_compute_tag(model.params) or "f32"
        self.steps = 0             # pairs the module ran (overhead meter)
        self.arena_bytes = 0       # no arena of its own

    def warmup(self) -> int:
        return 0

    def can_draft(self, prompt_len: int) -> bool:
        return True

    def release(self, slot: int) -> None:
        pass

    def release_all(self) -> None:
        pass

    def describe(self) -> dict:
        return {"kind": "prediction module", "shares_pool": True,
                "arena_layer": self.arena_layer, "dtype_tag": self.dtype_tag,
                "compute_mode": self.compute_mode,
                "block": {"mixer": self.model.mtp.mixer,
                          "mlp": self.model.mtp.mlp},
                "steps": self.steps}
