"""LM serving: continuous batching over a PAGED HBM-resident KV cache.

Offline ``generate()`` decodes one homogeneous batch in lockstep: every
prompt prefills together, every row steps together, and the batch
finishes when the SLOWEST request does — a serving workload with
staggered arrivals and mixed lengths wastes most of its FLOPs on
padding and waiting.  ``LMServingEngine`` is the iteration-level
(continuous) batching alternative (Orca, OSDI'22; the throughput model
vLLM popularized), built from fixed-shape device programs over the
paged block arena of :mod:`bigdl_tpu.serving.kvcache`:

- **prefill** — bucketed passes per new request through the shared
  :class:`CompileCache`.  A cold prompt runs the plain bucketed prefill
  (`_prefill_parts`); a prompt whose head is cached in the
  :class:`RadixCache` prefills only the unmatched SUFFIX against the
  cached block chain (`_prefill_suffix_parts`, one executable per
  (suffix bucket, prefix-chain bucket)); prompts longer than the
  largest bucket prefill in block-aligned CHUNKS — over-length requests
  are admitted, not rejected.
- **insert** — scatter of each chunk's k/v rows into its allocated
  blocks of the resident arenas (their layout is the pool's:
  :mod:`bigdl_tpu.serving.kvcache.blocks`), donated so insert rewrites
  the resident buffers in place.
- **decode** — ONE fixed-shape executable stepping all S slots, each at
  its own position, reading the blocks a **live list** names: each
  round ``_dispatch`` lists, on the host, the blocks its active slots hold
  up to their write positions (which block, whose, where in the chain:
  :func:`~bigdl_tpu.serving.kvcache.blocks.live_list`), padded to the
  ``S x M`` entries of whole tables; the step attends the list a chunk
  at a time (:func:`~bigdl_tpu.serving.kvcache.blocks.list_chunk`) and
  stops after the last chunk that holds a listed block, so the bytes a
  round gathers follow what the slots hold, not slots x the table
  width — paging and chain lengths change the operand, not the
  executable count — with ``donate_argnums`` on both arenas so the
  decode loop never copies HBM-resident state.
  ``LMMetrics.live_blocks`` / ``gathered_blocks`` count the blocks
  listed and the chunks' blocks gathered.  The step PICKS ITS TOKENS
  (``generate.pick_rows``: argmax, or the slot's key-chain draw at its
  temperature): a round hands the host ``(S,)`` ids, never ``(S, V)``
  logits (``LMMetrics.logit_rows_to_host`` counts the rows that do
  cross: an admission's first token, the verify rounds), and the host
  hands the round ONE operand vector -- tokens, positions,
  temperatures, keys and the live list
  (:func:`decode_operands`) -- because what a round pays around its
  device module is a fixed cost a transfer, not bytes.  A round RUNS ONE
  AHEAD: the step takes a slot's token from the previous step's ids on
  the device (``TAKE_PREV`` in the token's place), so the worker
  enqueues round n+1 (``_dispatch``) before it reads round n's ids
  (``_collect``) whenever nothing needs the host in between
  (``_runs_ahead``: nobody to seat, no chunk to prefill, nothing to
  hibernate, cancel or close), and collects first -- a drain --
  otherwise.  ``LMMetrics.rounds_ahead`` counts the rounds enqueued
  behind another, ``rows_discarded`` the rows of a stream that had ended
  on its ``eos`` a round before.

Recurrent layers: a model whose plan has ``mixer="kda"`` layers keeps,
beside the pool (built for its ATTENTION layers only), a **state arena**
(:mod:`bigdl_tpu.serving.kvcache.state`: one fixed-size row a slot and
recurrent layer).  Its arenas ride behind the pool's through the decode
step, donated; a prefill writes its slot's rows (``lm/state_insert``), a
chunk's continuation starts from them, and the radix prefix cache is off
(no state exists at a shared prefix's boundary).

Latent layers: a model whose plan has ``mixer="mla"`` layers caches ONE
LATENT ROW a position and layer, nothing a head: its pool is
``BlockPool(latent=True)``, one arena and no second (a model with no
``(k, v)`` layer gets no ``(k, v)`` arena at all), the step programs' donated
tuple is ``(rows,)`` + the state arenas, a prefill hands out rows
(``generate._insert_rows``) and reads a chunk's prefix from the arena, and
the decode step attends ABSORBED through the same live list.  What assumes a
``(k, v)`` pair is refused at construction (:func:`refuse_unsupported`).

Self-drafting: a model that states a PREDICTION MODULE
(``TransformerLM(mtp=...)``) and is given ``spec=SpecConfig(k=1)`` drafts
for itself (``serving.spec.SelfDrafter``): the module's block's rows are
one more layer of the latent arena (a prefill fills them beside the main
layers', one position on, so that a radix hit shares them with the
prefix), and a round is ONE program that verifies two candidate rows a
slot, picks, runs the module's pairs and drafts
(``generate._selfdraft_step_paged``): ``(S, 4)`` ids and counts reach the
host, a slot advances by 1 or 2, a rejected draft is a pointer rewind.
Such a round runs one ahead as a plain one does (``_dispatch_selfdraft`` /
``_collect_selfdraft``): a slot of the round on the device is CHAINED, its
tokens, how far it advanced and its ``n_cand`` taken from that round's
output on the device (``split_selfdraft_operands``), its live list
reaching the furthest it can have advanced; a slot that may have ended
with the round in flight sits the next one out, and a sampled slot keeps
the rounds synchronous (its keys follow how many tokens it emitted).

Sharing: the radix cache maps token prefixes to refcounted block
chains, so concurrent requests with a common head attend the SAME
blocks copy-free; decode always writes into a sequence's private tail
blocks (the trie only ever holds *full prompt* blocks, and generation
starts past them).  Pool pressure defers admissions (blocks free as
streams finish, and the trie LRU-evicts unreferenced tails) — only a
request whose total need exceeds the WHOLE pool is rejected, with the
typed :class:`~bigdl_tpu.serving.kvcache.RequestExceedsPool` counted
in ``serving/rejected_total``.

Correctness: a slot's token stream is the same computation offline
``generate()`` runs at batch 1 — cached prefix keys are stored
post-RoPE (rotated once at their own positions) so the suffix prefill
attends the identical valid key set through the identical attention
core, and decode masks gathered positions ``> pos`` so stale or
scratch rows are never attended.  The mixed-length soak asserts
token-exact agreement per request, greedy and sampled, sharing on.

Observability: TTFT and inter-token-latency histograms, tokens/sec,
slot occupancy (``serving/lm/*``) plus the paged-cache plane
(``kvcache/*``): block utilization, prefix hit rate, prefill tokens
saved, evictions, and the arena's HBM footprint
(``kvcache/arena_bytes``) — all in the process-wide registry, so
``ObsSummary`` and the SLO controller's headroom checks see cache
memory, not just slots.  The worker thread's round loop is tiled with
phase stamps (``ROUND_PHASES``; one ``perf_counter()`` read a boundary,
``_stamp``): always on, they feed the round record behind
``stats()["rounds"]`` — seconds by phase, the longest round with its own
split, one WARNING for a slow round — and, with the tracer on, the
``lm/round`` / ``lm/admit`` envelopes, one leaf span a phase and a
profiler annotation of the same name; ``shared_watchdog("lm_round")``
dumps every thread's stack while a round hangs.  The same stamps keep the
device's account (``_account``): a wait on the newest enqueued output proves it
empty, an enqueue ends that, and the phases between (bar ``lm/idle``) are
STARVED: ``starved_s`` / ``starved_phase_s``; tracer on, one ``lm/starved`` each.
"""
from __future__ import annotations

import logging
import operator
import statistics
import threading
import time
from collections import defaultdict, deque
from typing import List, Optional, Sequence

import numpy as np

from bigdl_tpu.obs import (env_watchdog_enabled, env_watchdog_kwargs,
                           get_registry, get_tracer, shared_watchdog)
from bigdl_tpu.obs.registry import FnGauge, Histogram
from bigdl_tpu.obs.tracer import mint_request_id
from bigdl_tpu.resilience.errors import (BackendLostError,
                                         ServingDeadlineExceeded,
                                         ServingOverloaded,
                                         TransientBackendError)
from bigdl_tpu.serving.batcher import (ServingClosed, ServingQueueFull,
                                       count_rejection)
from bigdl_tpu.serving.compile_cache import CompileCache
from bigdl_tpu.serving.kvcache import (BlockPool, PoolExhausted, RadixCache,
                                       RequestExceedsPool)
from bigdl_tpu.serving.kvcache.blocks import (class_entries, list_chunk,
                                              live_list, window_blocks)
from bigdl_tpu.utils.engine import configure_compile_cache

_tracer = get_tracer()
log = logging.getLogger("bigdl_tpu.serving")

#: the worker thread's leaf phases (span ``lm/<name>``) in the order a
#: round meets them: while anything is in flight every instant of the
#: thread lies in exactly one.  ``idle`` lies between rounds, the rest
#: inside one ``lm/round``.
ROUND_PHASES = ("idle", "sched", "admit_host", "prefill", "insert",
                "first_token", "draft", "decode_dispatch", "decode_wait",
                "emit", "tree_commit", "state_insert")
(P_IDLE, P_SCHED, P_ADMIT_HOST, P_PREFILL, P_INSERT, P_FIRST_TOKEN, P_DRAFT,
 P_DISPATCH, P_WAIT, P_EMIT, P_TREE_COMMIT,
 P_STATE_INSERT) = range(len(ROUND_PHASES))
_PHASE_SPANS = tuple("lm/" + p for p in ROUND_PHASES)
#: the phases that end with a program enqueued: the device has work from
#: the stamp that closes one on
_ENQUEUES = frozenset((P_PREFILL, P_INSERT, P_STATE_INSERT, P_DRAFT,
                       P_DISPATCH, P_TREE_COMMIT))
#: what the worker knows of the device (``LMServingEngine._dev``): something
#: it enqueued may still be there; a wait on the newest enqueued output has
#: proven it empty; or a wait has proven it empty up to an output with
#: more enqueued behind it, whose ``is_ready()`` the next stamps poll
_DEV_BUSY, _DEV_EMPTY, _DEV_POLL = 0, 1, 2
#: a round is logged as slow when it took this long AND this many
#: running medians of the plain (decode-only) rounds
SLOW_ROUND_S = 1.0
SLOW_ROUND_MEDIANS = 8.0


#: in a slot's place of ``token``: the step takes the slot's entry of the
#: previous step's ids, which are still on the device
TAKE_PREV = -1


def decode_operands(slots: int, entries: int):
    """What the host hands a decode round, as ONE int32 vector and the
    views a round fills: ``-> (operands, token (S,), pos (S,),
    temperature (S,) float32, keys (S, 2) uint32, live (3, entries))``.
    A slot's ``token`` is its last token, or ``TAKE_PREV`` where that
    token is the previous round's pick and has not reached the host.
    One vector because every host operand of a step costs its dispatch a
    transfer of its own (0.13-0.24 ms each on a v5e, PERF.md PR 31),
    whatever its size; a fresh one a round because the transfer may still
    read it when the call returns.  :func:`split_decode_operands` is the
    same layout on the device."""
    s = int(slots)
    ops = np.zeros((5 * s + 3 * int(entries),), np.int32)
    return (ops, ops[:s], ops[s:2 * s], ops[2 * s:3 * s].view(np.float32),
            ops[3 * s:5 * s].view(np.uint32).reshape(s, 2),
            ops[5 * s:].reshape(3, -1))


def split_decode_operands(ops, slots: int):
    """:func:`decode_operands`' vector inside the step program ->
    ``(token, pos, temperature, keys, live)``."""
    import jax.numpy as jnp
    from jax import lax
    s = int(slots)
    return (ops[:s], ops[s:2 * s],
            lax.bitcast_convert_type(ops[2 * s:3 * s], jnp.float32),
            lax.bitcast_convert_type(ops[3 * s:5 * s],
                                     jnp.uint32).reshape(s, 2),
            ops[5 * s:].reshape(3, -1))


def selfdraft_operands(slots: int, entries: int):
    """:func:`decode_operands` for a SELF-DRAFTING round (the model's own
    prediction module as the drafter), one int32 vector as well: ``->
    (operands, tokens (S, 2) [last emitted, draft], pos (S,), n_cand (S,),
    fresh (S,), temperature (S,) float32, keys (S, 4, 2) uint32, live (3,
    entries), chain (S,), remaining (S,))``.  A slot with ``chain`` set
    rides a round enqueued BEHIND the one on the device: its ``pos`` and
    ``remaining`` (tokens left of its count) are as of before that round,
    and the step takes its tokens, its position and its ``n_cand`` from
    that round's ``(S, 4)`` output, which has not reached the host
    (:func:`split_selfdraft_operands` on the device)."""
    s = int(slots)
    ops = np.zeros((16 * s + 3 * int(entries),), np.int32)
    return (ops, ops[:2 * s].reshape(s, 2), ops[2 * s:3 * s],
            ops[3 * s:4 * s], ops[4 * s:5 * s],
            ops[5 * s:6 * s].view(np.float32),
            ops[6 * s:14 * s].view(np.uint32).reshape(s, 4, 2),
            ops[16 * s:].reshape(3, -1), ops[14 * s:15 * s],
            ops[15 * s:16 * s])


def split_selfdraft_operands(ops, slots: int, prev=None):
    """-> ``(tokens, pos, n_cand, fresh (bool), temperature, keys, live)``.
    ``prev`` (S, 4) is the previous round's output ``[y0, y1, accepted,
    draft]``, still on the device: a ``chain`` slot emitted ``1 +
    accepted`` tokens in it, so its last token is ``y1`` where the draft was
    accepted and ``y0`` where not, at that many positions further; it
    verifies that round's draft where two tokens or more are left of its
    count (the host's own rule for ``n_cand``), and the host hands such a
    round only slots with one left at least."""
    import jax.numpy as jnp
    from jax import lax
    s = int(slots)
    tokens, pos, n_cand = ops[:2 * s].reshape(s, 2), ops[2 * s:3 * s], ops[3 * s:4 * s]
    if prev is not None:
        chain, remaining = ops[14 * s:15 * s] > 0, ops[15 * s:16 * s]
        accepted = prev[:, 2]
        last = jnp.where(accepted > 0, prev[:, 1], prev[:, 0])
        tokens = jnp.where(chain[:, None],
                           jnp.stack([last, prev[:, 3]], axis=1), tokens)
        pos = pos + jnp.where(chain, 1 + accepted, 0)
        n_cand = jnp.where(
            chain, jnp.where(remaining - 1 - accepted >= 2, 2, 1), n_cand)
    return (tokens, pos, n_cand, ops[4 * s:5 * s] > 0,
            lax.bitcast_convert_type(ops[5 * s:6 * s], jnp.float32),
            lax.bitcast_convert_type(ops[6 * s:14 * s],
                                     jnp.uint32).reshape(s, 4, 2),
            ops[16 * s:].reshape(3, -1))


#: what a cache kind other than a paged ``(k, v)`` pair cannot do, and what
#: the model's own prediction module cannot do as the drafter: a
#: row a (kind, what is asked, why); the next cache kind adds rows, not branches
_KIND_NAMES = {"recurrent": ("recurrent layers", "M6"),
               "latent": ("latent attention layers", "M4"),
               "windowed": ("layers whose window lets go of what lies "
                            "behind it", "M3"),
               "classes": ("softmax layers of several kinds (a class of "
                           "blocks each)", "M3"),
               "self-drafting": ("its prediction module as the drafter",
                                 "M5")}
_REFUSALS = (
    ("self-drafting", "serve with tree verify", "one module drafts one token "
     "a round: there is no runner-up to branch on"),
    ("self-drafting", "serve with rejection sampling", "the round picks on "
     "the device with the slot's own keys (replay), and hands the host no "
     "drafter distribution to form p / q from"),
    ("self-drafting", "serve with k > 1", "one prediction module scores one "
     "token past the next"),
    ("recurrent", "serve with spec", "a rejected draft would need the "
     "recurrent state rolled back, and only the K/V pointer rewinds"),
    ("recurrent", "serve with migrate", "the handoff carries (k, v) chains, "
     "not a recurrent layer's state"),
    ("recurrent", "serve with kvtier", "demotion, promotion and hibernation "
     "carry (k, v) blocks, not a recurrent layer's state"),
    ("recurrent", "serve with tensor-parallel placement", "no rule places a "
     "recurrent layer's heads and state"),
    ("recurrent", "adopt a migrated request", "the handoff carries (k, v) "
     "chains, not a recurrent layer's state"),
    ("latent", "serve with kv_quant='int8'", "the int8 pool keeps a scale a "
     "(position, head), and a latent row has no head"),
    ("latent", "serve with tree verify", "the accepted path's commit moves "
     "(k, v) rows; none is written for a latent row"),
    ("latent", "serve with migrate", "the handoff's wire format is a (k, v) "
     "pair, and the pool holds one latent row a position"),
    ("latent", "serve with kvtier", "the host tier's wire format is a (k, v) "
     "pair, and the pool holds one latent row a position"),
    ("latent", "serve with tensor-parallel placement", "no rule places the "
     "up-projections' heads"),
    ("latent", "adopt a migrated request", "the handoff carries (k, v) "
     "chains, and the pool holds one latent row a position"),
    ("windowed", "serve with migrate", "a windowed class has let go of the "
     "blocks behind the window: there is no whole chain to hand off"),
    ("windowed", "serve with kvtier", "demotion, promotion and hibernation "
     "carry whole chains, and a windowed class has let go of what lies "
     "behind the window"),
    ("windowed", "adopt a migrated request", "the handoff carries whole "
     "chains, and a windowed class holds a window's blocks"),
    ("classes", "serve with tree verify", "the accepted path's commit moves "
     "one class's rows"),
    ("classes", "serve with migrate", "the handoff's wire format is one "
     "(k, v) pair of one width"),
    ("classes", "serve with kvtier", "the host tier's wire format is one "
     "(k, v) pair of one width"),
    ("classes", "adopt a migrated request", "the handoff carries one (k, v) "
     "pair of one width"),
)


def drafts_for_itself(model, spec) -> bool:
    """Whether ``spec`` (a ``SpecConfig``, an int k, or None) makes the
    model's own prediction module the drafter: the model has one, and no
    other drafter is named (no ``draft`` model, not the n-gram one)."""
    if spec is None or getattr(model, "mtp", None) is None:
        return False
    return (isinstance(spec, int)
            or (spec.draft is None
                and getattr(spec, "drafter_compute", None) != "ngram"))


def refuse_unsupported(model, *, spec=None, migrate=None, kvtier=None,
                       kv_quant=None, placement=None, adopt=False):
    """Raise, with its one message, for the first thing asked that the
    model's cache kinds, or its own prediction module as the drafter, cannot
    do (:data:`_REFUSALS`): THE place where a recurrent state and a latent
    pool refuse what assumes a paged ``(k, v)`` pair, and a self-drafting
    model what one module cannot draft, at construction and where a handoff
    arrives (``adopt``)."""
    classes = model.cache_classes
    has = {"recurrent": bool(model.state_layers),
           "latent": bool(model.latent_layers),
           "windowed": any(c.window is not None for c in classes),
           "classes": len(classes) > 1,
           "self-drafting": drafts_for_itself(model, spec)}
    k = spec if isinstance(spec, int) else getattr(spec, "k", 1)
    asked = {"serve with spec": spec is not None,
             "serve with tree verify": bool(getattr(spec, "tree", False)),
             "serve with rejection sampling":
                 getattr(spec, "sampling", "replay") == "rejection",
             "serve with k > 1": spec is not None and k > 1,
             "serve with migrate": migrate is not None,
             "serve with kvtier": kvtier is not None,
             "serve with kv_quant='int8'": kv_quant == "int8",
             "serve with tensor-parallel placement":
                 placement is not None and placement.tp > 1,
             "adopt a migrated request": adopt}
    for kind, what, why in _REFUSALS:
        if has[kind] and asked[what]:
            name, milestone = _KIND_NAMES[kind]
            raise ValueError(f"a model with {name} cannot {what}: {why} "
                             f"(ROADMAP {milestone})")


def prefill_bucket_lengths(max_len: int, min_bucket: int = 8) -> tuple:
    """Power-of-two prompt-length buckets up to (and including) a
    non-power-of-two ``max_len`` cap."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    buckets = []
    b = max(1, int(min_bucket))
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_len))
    return tuple(sorted(set(buckets)))


# ---------------------------------------------------------------------- #
class StreamTruncation:
    """Typed marker for a stream the lifecycle layer ended early.

    Attached as ``LMStream.truncation`` when a mid-stream deadline
    expiry or a cooperative cancel finishes the stream: the tokens
    already emitted stay valid (and bit-exact), the stream completes
    WITHOUT an error, and the marker records why and where it stopped.
    ``reason`` is ``"deadline"`` or ``"cancelled"``."""

    __slots__ = ("reason", "at_tokens", "deadline_s")

    def __init__(self, reason: str, at_tokens: int,
                 deadline_s: Optional[float] = None):
        self.reason = str(reason)
        self.at_tokens = int(at_tokens)  # generated length at truncation
        self.deadline_s = deadline_s     # original budget, if any

    def __repr__(self):
        return (f"StreamTruncation(reason={self.reason!r}, "
                f"at_tokens={self.at_tokens})")


class LMStream:
    """Per-request handle: tokens stream in as the engine decodes them.

    ``tokens()`` iterates 1-based generated ids as they land;
    ``result()`` blocks for the full sequence (prompt + generated).
    Timing marks (submit / first token / finish) feed the TTFT and
    inter-token-latency metrics and are readable per request.

    Lifecycle: an optional wall-clock budget (``deadline_s``, armed at
    enqueue) and a public :meth:`cancel`.  Both are COOPERATIVE — the
    engine honors them at its next scheduler round, recycling the
    decode slot and KV blocks and finishing the stream with a typed
    :class:`StreamTruncation` marker (already-emitted tokens stay
    valid; ``result()`` returns them without raising).
    """

    def __init__(self, prompt_1b: np.ndarray, max_new: int,
                 request_id: Optional[str] = None,
                 deadline_s: Optional[float] = None):
        self.prompt = prompt_1b
        self.max_new = int(max_new)
        self.request_id = request_id    # trace/flight correlation handle
        self._tokens: List[int] = []
        self._cond = threading.Condition()
        self._done = False
        self._error: Optional[BaseException] = None
        self.submitted_at = time.perf_counter()
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # --- lifecycle ---------------------------------------------- #
        self.deadline_s = (float(deadline_s)
                           if deadline_s is not None else None)
        # absolute wall-clock deadline, minted at construction so the
        # remaining budget (not a reset one) rides every re-dispatch,
        # KV handoff, and hibernate/resume hop
        self.deadline_at = ((time.monotonic() + self.deadline_s)
                            if self.deadline_s is not None else None)
        self.truncation: Optional[StreamTruncation] = None
        self._cancel_requested = False
        self._cancel_at_gen = 0         # generated length when cancelled
        self._wake_cb = None            # engine nudge, set at enqueue
        #: a self-drafting engine's record: ``(i, id)`` for every draft it
        #: verified -- the 1-based id its prediction module drafted for
        #: generated token ``i`` (accepted iff that token is the id)
        self.drafts: List[tuple] = []

    # lifecycle ---------------------------------------------------------- #
    def cancel(self) -> bool:
        """Request cooperative cancellation (client disconnected /
        stopped caring).  Returns True if the request was still live;
        the engine honors it at the next scheduler round.  Idempotent
        and safe from any thread."""
        with self._cond:
            if self._done:
                return False
            if not self._cancel_requested:
                self._cancel_requested = True
                self._cancel_at_gen = len(self._tokens)
            cb = self._wake_cb
        if cb is not None:
            try:
                cb()
            except Exception:   # a closing engine must not fail cancel
                pass
        return True

    @property
    def cancel_requested(self) -> bool:
        with self._cond:
            return self._cancel_requested

    def expired(self, now: Optional[float] = None) -> bool:
        """True when the wall-clock budget is spent."""
        if self.deadline_at is None:
            return False
        return (now if now is not None else time.monotonic()) \
            >= self.deadline_at

    def remaining_s(self, now: Optional[float] = None) -> Optional[float]:
        """Budget left (seconds; may be negative), or None if unbounded."""
        if self.deadline_at is None:
            return None
        return self.deadline_at - (now if now is not None
                                   else time.monotonic())

    # engine-side ------------------------------------------------------- #
    def _emit(self, token_1b: int) -> None:
        with self._cond:
            if self.first_token_at is None:
                self.first_token_at = time.perf_counter()
            self._tokens.append(int(token_1b))
            self._cond.notify_all()

    def _finish(self, error: Optional[BaseException] = None) -> None:
        with self._cond:
            if self._done:
                return
            self._done = True
            self._error = error
            self.finished_at = time.perf_counter()
            self._cond.notify_all()

    def _finish_truncated(self, reason: str) -> None:
        """Finish early with a typed truncation marker (no error): the
        tokens already emitted remain the valid, bit-exact prefix of
        what the full decode would have produced."""
        with self._cond:
            if self._done:
                return
            if self.truncation is None:
                self.truncation = StreamTruncation(
                    reason, len(self._tokens), self.deadline_s)
        self._finish()

    # client-side ------------------------------------------------------- #
    def tokens(self, timeout: Optional[float] = None):
        """Yield generated 1-based token ids as they arrive."""
        deadline = (time.perf_counter() + timeout) if timeout else None
        i = 0
        while True:
            with self._cond:
                while len(self._tokens) <= i and not self._done:
                    left = (deadline - time.perf_counter()) if deadline \
                        else None
                    if left is not None and left <= 0:
                        raise TimeoutError("LMStream.tokens timed out")
                    self._cond.wait(left)
                if len(self._tokens) > i:
                    tok = self._tokens[i]
                    i += 1
                elif self._error is not None:
                    raise self._error
                else:
                    return
            yield tok

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until done; return prompt + generated ids (1-based)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError("LMStream.result timed out")
            if self._error is not None:
                raise self._error
            gen = np.asarray(self._tokens, np.int32)
        return np.concatenate([self.prompt, gen])

    def done(self) -> bool:
        with self._cond:
            return self._done

    @property
    def generated(self) -> np.ndarray:
        with self._cond:
            return np.asarray(self._tokens, np.int32)

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


# ---------------------------------------------------------------------- #
class LMMetrics:
    """Serving-LM counters; thread-safe (decode worker + callers).

    Occupancy is measured where continuous batching earns its keep: the
    fraction of slot-iterations that decoded a real request (a lockstep
    engine pays for every slot every step regardless).

    ITL is split per phase: ``itl`` stays the combined histogram every
    existing consumer (SLO controller, the benchmark) reads, while
    ``itl_decode`` holds only gaps between back-to-back decode rounds
    and ``itl_prefill_gap`` the gaps a prefill (or a KV-chain adoption)
    interrupted — the head-of-line blocking disaggregation exists to
    remove, now measurable straight from the registry
    (``serving/lm/itl_decode`` vs ``serving/lm/itl_prefill_gap``).

    The round record (``record_round`` / ``rounds_snapshot``) is what the
    engine's phase stamps feed whether or not the tracer is on: rounds
    counted, seconds summed per phase and, of them, the seconds that
    passed with the device proven empty (``starved_phase_s``), the last
    plain rounds for a running median, and the longest round with its own
    phase split.  It costs a fixed handful of additions a round."""

    def __init__(self, slots: int, throughput_window_s: float = 60.0):
        self._lock = threading.Lock()
        self.slots = int(slots)
        self.spec = None  # SpecMetrics when the engine speculates
        self.ttft = Histogram()
        self.itl = Histogram()
        self.itl_decode = Histogram()
        self.itl_prefill_gap = Histogram()
        self.requests = 0
        self.rejected = 0
        self.completed = 0
        self.tokens = 0
        self.prefills = 0
        self.decode_steps = 0
        # the decode rounds' live lists: blocks the active slots held, and
        # blocks of the chunks that held them (what a round gathers);
        # ``decode_steps`` x slots x table width is what whole tables hold
        self.live_blocks = 0
        self.gathered_blocks = 0
        # rows of V float32 logits copied to the host: 1 an admission's
        # first token, S x W a verify round, none for a plain decode
        # round (its step picks on the device and hands out S ids)
        self.logit_rows_to_host = 0
        # decode rounds enqueued while their predecessor was still on the
        # device (of ``decode_steps``), and rows of such a round thrown
        # away because their stream had ended on its eos a round before
        self.rounds_ahead = 0
        self.rows_discarded = 0
        self.slot_steps = 0
        self.active_slot_steps = 0
        self.peak_active = 0
        # routed expert layers (zero for a dense model): (token, expert)
        # assignments that landed on held experts, distinct held experts
        # hit summed over layers and rounds, and expert-layer rounds run
        self.moe_assignments = 0
        self.moe_experts_hit = 0
        self.moe_expert_layer_rounds = 0
        self.moe_prefill_assignments = 0
        # a router with groups (zero otherwise): the groups in which a
        # decoded token has a chosen expert, summed over tokens, layers, rounds
        self.moe_groups_hit = 0
        # row tiles the grouped matmul's kernel visited (zero on the other
        # path: parallel.expert.grouped_swiglu), over layers and rounds
        self.moe_row_tiles = 0
        # latent layers (zero for a model without): (position, latent layer)
        # rows the decode rounds' live slots held -- what their attention
        # read -- and those rows' bytes as the arena holds them
        self.latent_rows_read = 0
        self.latent_bytes_read = 0
        self.latent_row_bytes = 0
        # admissions: prompt tokens admitted and, of them, those a radix hit
        # found cached (what the prefills did not compute)
        self.prompt_tokens = 0
        self.prefix_matched_tokens = 0
        # recurrent layers (zero for a model without): (slot, recurrent
        # layer) rows the decode rounds read and wrote, the rows that hold a
        # seated request's state now, and the state arena's bytes
        self.state_row_steps = 0
        self.state_rows_in_use = 0
        self.state_bytes = 0
        # softmax layers by class of blocks: positions a layer of a class
        # without a window may see in the decode rounds, summed over active
        # slots and rounds, and the same of a class with one (min(context,
        # window) a slot); blocks of the windowed classes let go behind a
        # window and allotted ahead of it; the most a decoding sequence held
        # of one after a round's release; and what the active slots held of
        # them against what their contexts span, summed over rounds
        self.decode_context_tokens = 0
        self.decode_window_tokens = 0
        self.window_blocks_released = 0
        self.window_blocks_allotted = 0
        self.window_blocks_held_max = 0
        self.window_blocks_held = 0
        self.window_blocks_spanned = 0
        self.started_at = time.perf_counter()
        self._window_s = float(throughput_window_s)
        self._recent: deque = deque()  # (t, n_tokens) per decode step
        self._started_unix = time.time()
        self.reset_rounds()

    def publish_to(self, registry,
                   prefix: str = "serving/lm/") -> "LMMetrics":
        registry.register(prefix + "ttft", self.ttft, replace=True)
        registry.register(prefix + "itl", self.itl, replace=True)
        registry.register(prefix + "itl_decode", self.itl_decode,
                          replace=True)
        registry.register(prefix + "itl_prefill_gap", self.itl_prefill_gap,
                          replace=True)
        for key in ("requests", "rejected", "completed", "tokens",
                    "prefills", "decode_steps", "live_blocks",
                    "gathered_blocks", "logit_rows_to_host",
                    "rounds_ahead", "rows_discarded",
                    "decode_context_tokens", "decode_window_tokens",
                    "window_blocks_released", "window_blocks_held_max"):
            registry.register(prefix + key,
                              FnGauge(lambda k=key: getattr(self, k)),
                              replace=True)
        for key in ("latent_rows_read", "latent_bytes_read", "prompt_tokens",
                    "prefix_matched_tokens"):
            registry.register(prefix + key,
                              FnGauge(lambda k=key: getattr(self, k)),
                              replace=True)
        for key in ("assignments", "experts_hit", "expert_layer_rounds",
                    "prefill_assignments", "groups_hit", "row_tiles"):
            registry.register(
                prefix + "moe/" + key,
                FnGauge(lambda k="moe_" + key: getattr(self, k)),
                replace=True)
        for key in ("row_steps", "rows_in_use", "bytes"):
            registry.register(
                prefix + "state/" + key,
                FnGauge(lambda k="state_" + key: getattr(self, k)),
                replace=True)
        registry.register(prefix + "tokens_per_s",
                          FnGauge(lambda: self.snapshot()["tokens_per_s"]),
                          replace=True)
        registry.register(
            prefix + "slot_occupancy",
            FnGauge(lambda: self.snapshot()["slot_occupancy"]),
            replace=True)
        registry.register(
            prefix + "slot_occupancy_peak",
            FnGauge(lambda: self.snapshot()["slot_occupancy_peak"]),
            replace=True)
        return self

    # -- recording ------------------------------------------------------ #
    def record_submit(self) -> None:
        with self._lock:
            self.requests += 1

    def record_reject(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_first_token(self, ttft_s: float) -> None:
        with self._lock:
            self.prefills += 1
            self.tokens += 1
            self.ttft.observe(ttft_s)
            self._recent.append((time.perf_counter(), 1))

    def record_step(self, n_active: int, itls_s: Sequence[float],
                    prefill_interrupted: bool = False, *,
                    live_blocks: int = 0, gathered_blocks: int = 0,
                    state_rows: int = 0, latent_rows: int = 0,
                    ahead: bool = False, discarded: int = 0,
                    ctx_tokens: int = 0, window_tokens: int = 0,
                    window_held: int = 0, window_spanned: int = 0,
                    window_held_max: int = 0) -> None:
        with self._lock:
            now = time.perf_counter()
            self.decode_steps += 1
            self.decode_context_tokens += ctx_tokens
            self.decode_window_tokens += window_tokens
            self.window_blocks_held += window_held
            self.window_blocks_spanned += window_spanned
            self.window_blocks_held_max = max(self.window_blocks_held_max,
                                              window_held_max)
            self.rounds_ahead += ahead
            self.rows_discarded += discarded
            self.live_blocks += live_blocks
            self.gathered_blocks += gathered_blocks
            self.state_row_steps += state_rows
            self.latent_rows_read += latent_rows
            self.latent_bytes_read += latent_rows * self.latent_row_bytes
            self.slot_steps += self.slots
            self.active_slot_steps += n_active
            self.peak_active = max(self.peak_active, n_active)
            self.tokens += len(itls_s)
            self._recent.append((now, len(itls_s)))
            horizon = now - self._window_s
            while self._recent and self._recent[0][0] < horizon:
                self._recent.popleft()
            split = (self.itl_prefill_gap if prefill_interrupted
                     else self.itl_decode)
            for itl in itls_s:
                self.itl.observe(itl)
                split.observe(itl)

    def record_complete(self) -> None:
        with self._lock:
            self.completed += 1

    def record_window(self, released: int, allotted: int) -> None:
        """Blocks of the windowed classes a sequence let go of behind its
        window, and was allotted ahead of it."""
        with self._lock:
            self.window_blocks_released += int(released)
            self.window_blocks_allotted += int(allotted)

    def record_admission(self, prompt_tokens: int, matched: int) -> None:
        """A request got its blocks: its prompt's length and how much of it
        the radix cache held."""
        with self._lock:
            self.prompt_tokens += int(prompt_tokens)
            self.prefix_matched_tokens += int(matched)

    def record_logit_rows(self, rows: int) -> None:
        """``rows`` rows of V float32 logits were copied to the host."""
        with self._lock:
            self.logit_rows_to_host += int(rows)

    def record_moe(self, counts, layers: int) -> None:
        """One decode step's routed-layer integers (summed over its
        ``layers`` expert layers by the step program; the groups hit ride
        third where the router has groups, the row tiles visited last)."""
        with self._lock:
            self.moe_assignments += int(counts[0])
            self.moe_experts_hit += int(counts[1])
            if len(counts) > 3:
                self.moe_groups_hit += int(counts[2])
            self.moe_row_tiles += int(counts[-1])
            self.moe_expert_layer_rounds += int(layers)

    def record_moe_prefill(self, assignments: int) -> None:
        """A prompt's assignments that landed on held experts."""
        with self._lock:
            self.moe_prefill_assignments += int(assignments)

    def reset_rounds(self) -> None:
        """Start the round record afresh (a measured window opens)."""
        with self._lock:
            self.rounds = 0
            self.plain_rounds = 0
            self.slow_rounds = 0
            self.phase_s = [0.0] * len(ROUND_PHASES)
            self.starved_phase_s = [0.0] * len(ROUND_PHASES)
            self._plain: deque = deque(maxlen=64)
            self._longest = None

    def record_round(self, t0: float, dur_s: float, split: list,
                     index: int, active: int, admitted: int,
                     plain: bool, starved: Optional[list] = None
                     ) -> Optional[float]:
        """Fold one finished round in: ``split`` is its seconds per
        phase (indexed as ``ROUND_PHASES``; the record keeps the list),
        ``starved`` the part of them that passed with the device proven
        empty (None: none did).
        Returns the running median of the plain rounds when this round
        is a slow one (``SLOW_ROUND_S`` and ``SLOW_ROUND_MEDIANS``), for
        the engine to log, else None."""
        median = None
        with self._lock:
            self.rounds += 1
            self.phase_s = list(map(operator.add, self.phase_s, split))
            if starved is not None:
                self.starved_phase_s = list(map(
                    operator.add, self.starved_phase_s, starved))
            if self._longest is None or dur_s > self._longest[1]:
                self._longest = (t0, dur_s, split, index, active, admitted,
                                 starved)
            if dur_s >= SLOW_ROUND_S and self._plain:
                median = statistics.median(self._plain)
                if dur_s >= SLOW_ROUND_MEDIANS * median:
                    self.slow_rounds += 1
                else:
                    median = None
            if plain:
                self.plain_rounds += 1
                self._plain.append(dur_s)
        return median

    # -- reading -------------------------------------------------------- #
    def rounds_snapshot(self) -> dict:
        """The round record since start or the last ``reset_rounds()``."""
        with self._lock:
            out = {"count": self.rounds, "plain": self.plain_rounds,
                   "slow": self.slow_rounds,
                   "median_plain_s": (statistics.median(self._plain)
                                      if self._plain else None),
                   "phase_s": dict(zip(ROUND_PHASES, self.phase_s)),
                   "starved_s": sum(self.starved_phase_s),
                   "starved_phase_s": dict(zip(ROUND_PHASES,
                                               self.starved_phase_s)),
                   "longest": None}
            longest = self._longest
        if longest is not None:
            t0, dur_s, split, index, active, admitted, starved = longest
            in_round = dict(zip(ROUND_PHASES[1:], split[1:]))
            out["longest"] = {
                "seconds": dur_s, "round": index,
                "starved_s": sum(starved) if starved else 0.0,
                "at_s": t0 - self.started_at,
                "at_unix": self._started_unix + (t0 - self.started_at),
                "active": active, "admitted": admitted,
                "phase": max(in_round, key=in_round.get),
                "phase_s": in_round}
        return out

    def snapshot(self) -> dict:
        with self._lock:
            now = time.perf_counter()
            horizon = now - self._window_s
            while self._recent and self._recent[0][0] < horizon:
                self._recent.popleft()
            span = min(now - self.started_at, self._window_s)
            windowed = sum(n for _, n in self._recent)
            return {
                "requests": self.requests,
                "rejected": self.rejected,
                "completed": self.completed,
                "tokens": self.tokens,
                "prefills": self.prefills,
                "decode_steps": self.decode_steps,
                "live_blocks": self.live_blocks,
                "gathered_blocks": self.gathered_blocks,
                "logit_rows_to_host": self.logit_rows_to_host,
                "rounds_ahead": self.rounds_ahead,
                "rows_discarded": self.rows_discarded,
                "moe": {"assignments": self.moe_assignments,
                        "experts_hit": self.moe_experts_hit,
                        "expert_layer_rounds": self.moe_expert_layer_rounds,
                        "prefill_assignments": self.moe_prefill_assignments,
                        "groups_hit": self.moe_groups_hit,
                        "row_tiles": self.moe_row_tiles},
                "prefix": {"prompt_tokens": self.prompt_tokens,
                           "matched_tokens": self.prefix_matched_tokens},
                "latent": {"rows_read": self.latent_rows_read,
                           "bytes_read": self.latent_bytes_read,
                           "row_bytes": self.latent_row_bytes},
                "state": {"row_steps": self.state_row_steps,
                          "rows_in_use": self.state_rows_in_use,
                          "bytes": self.state_bytes},
                "decode_context_tokens": self.decode_context_tokens,
                "decode_window_tokens": self.decode_window_tokens,
                "window_blocks_released": self.window_blocks_released,
                "window_blocks_allotted": self.window_blocks_allotted,
                "window_blocks_held_max": self.window_blocks_held_max,
                "window_blocks_held": self.window_blocks_held,
                "window_blocks_spanned": self.window_blocks_spanned,
                "tokens_per_s": (windowed / span) if span > 0 else 0.0,
                "slot_occupancy":
                    (self.active_slot_steps / self.slot_steps)
                    if self.slot_steps else None,
                "slot_occupancy_peak":
                    (self.peak_active / self.slots)
                    if self.slot_steps else None,
                "ttft": self.ttft.snapshot(),
                "itl": self.itl.snapshot(),
                "itl_decode": self.itl_decode.snapshot(),
                "itl_prefill_gap": self.itl_prefill_gap.snapshot(),
                "spec": (self.spec.snapshot()
                         if self.spec is not None else None),
            }


# ---------------------------------------------------------------------- #
class _Request:
    __slots__ = ("stream", "prompt0", "max_new", "temperature", "eos0",
                 "first_key", "step_keys", "rid")

    def __init__(self, stream, prompt0, max_new, temperature, eos0,
                 first_key, step_keys, rid):
        self.stream = stream
        self.prompt0 = prompt0          # (t,) int32, 0-based
        self.max_new = max_new
        self.temperature = temperature
        self.eos0 = eos0                # 0-based eos id or None
        self.first_key = first_key      # np (2,) uint32 or None
        self.step_keys = step_keys      # np (max_new-1, 2) or None
        self.rid = rid                  # request id (tracing/forensics)


class _Slot:
    __slots__ = ("stream", "pos_next", "last0", "remaining", "step_idx",
                 "temperature", "eos0", "step_keys", "last_emit_at",
                 "blocks", "table", "draft_ok", "demoted", "accept_ema",
                 "spec_rounds", "probe_in", "tree_rung", "rid", "replay",
                 "draft", "fresh", "marks")

    def __init__(self, req: _Request, prompt_len: int, first0: int,
                 blocks: List[int], table: np.ndarray, marks=None):
        self.stream = req.stream
        self.rid = req.rid
        self.pos_next = prompt_len      # next cache position to write
        self.last0 = first0             # last emitted token, 0-based
        self.remaining = req.max_new - 1
        self.step_idx = 0               # index into step_keys
        self.temperature = req.temperature
        self.eos0 = req.eos0
        self.step_keys = req.step_keys
        self.last_emit_at = time.perf_counter()
        self.blocks = blocks            # one pool ref per block
        self.table = table              # (C, M) int32, scratch-padded
        # a windowed class's first entry not yet let go (BlockPool.advance)
        self.marks = marks if marks is not None else defaultdict(int)
        # already-emitted 0-based tokens whose KV the decode loop must
        # rebuild (payload-less resume): forced through decode without
        # re-emitting, so the rebuilt rows ride the exact path that
        # wrote the originals
        self.replay: deque = deque()
        # speculation state (spec engines only)
        self.draft_ok = False           # drafter holds this slot's KV
        self.demoted = False            # plain decode until re-probe
        self.accept_ema = None          # acceptance-rate EMA
        self.spec_rounds = 0            # rounds of EMA evidence
        self.probe_in = 0               # plain rounds until re-probe
        self.tree_rung = 0              # shape-ladder rung (tree mode)
        # self-drafting: the prediction module's draft for the position
        # after ``last0``'s (None: none yet), and whether the slot comes
        # straight from its prefill (its first pair is still to run)
        self.draft = None
        self.fresh = True


class _Round:
    """A decode round on the device: what ``_dispatch`` leaves for
    ``_collect``.  ``rows`` are ``(slot, its _Slot, emits, last)``: a
    replayed row emits nothing, ``last`` is the row of a stream that
    finishes by its count.  A self-drafting round's are ``(slot, its
    _Slot, n_cand)``, ``None`` for a chained row, whose ``n_cand`` the step
    worked out on the device."""

    __slots__ = ("ids", "moe", "rows", "t0", "ahead", "n_live", "gathered",
                 "n_last", "n_positions", "sampled", "ctx_tokens",
                 "window_tokens", "window_held", "window_spanned",
                 "window_held_max")

    def __init__(self, t0: float, ahead: bool):
        self.ids = self.moe = None      # device arrays, on their way
        self.rows: list = []
        self.t0 = t0                    # when its dispatch began
        self.ahead = ahead              # enqueued behind a round in flight
        self.n_live = self.gathered = self.n_last = 0
        self.n_positions = 0            # live positions its slots hold
        # positions a layer of its slots may see, summed over the slots: of a
        # class without a window, and of one with (min(context, window))
        self.ctx_tokens = self.window_tokens = 0
        # blocks of the windowed classes its slots hold / their contexts span
        self.window_held = self.window_spanned = self.window_held_max = 0
        self.sampled: list = []         # (rid, slot, step) of traced requests


class KVHandoff:
    """One request mid-migration between phase replicas.

    The prefill replica builds it after emitting the first token (TTFT
    belongs to the prefill side); the coordinator fills ``payload``
    (the exported block-major wire arrays — or None to re-prefill on
    the decode side) and ``matched`` (blocks the DECODE pool's radix
    already held for this prompt, retained for the adoption, so prefix
    sharing survives the hop and only the unmatched tail travels); the
    decode replica consumes it via :meth:`LMServingEngine.adopt`.
    Sampling state (``step_keys``, position, last token) crosses intact
    — the decode side continues the exact offline trajectory."""

    __slots__ = ("stream", "prompt0", "max_new", "temperature", "eos0",
                 "step_keys", "rid", "first0", "payload", "matched",
                 "src_name")

    def __init__(self, req: "_Request", first0: int, src_name: str):
        self.stream = req.stream
        self.prompt0 = req.prompt0
        self.max_new = req.max_new
        self.temperature = req.temperature
        self.eos0 = req.eos0
        self.step_keys = req.step_keys
        self.rid = req.rid
        self.first0 = int(first0)       # already emitted; never re-emit
        self.payload = None             # {"k","v","blocks"} wire or None
        self.matched = []               # decode-pool blocks, pre-retained
        self.src_name = src_name


class _Hibernated:
    """One stream swapped out of its decode slot into the host KV
    tier — the hibernation analogue of :class:`KVHandoff`.  Carries
    the full sampling/position state (``pos_next``, ``last0``,
    ``remaining``, ``step_idx``, ``step_keys``) so resume re-enters
    decode at the exact token the slot left off; the KV chain itself
    lives in the :class:`~bigdl_tpu.serving.kvtier.HostBlockStore`
    under ``("session", rid)`` until resume pops it.  ``payload`` is
    populated at resume time (and kept across a pool-pressure
    deferral, so a popped chain is never re-read or lost)."""

    __slots__ = ("stream", "rid", "pos_next", "last0", "remaining",
                 "step_idx", "temperature", "eos0", "step_keys",
                 "n_used", "payload", "fetched", "hibernated_at")

    def __init__(self, st: "_Slot", n_used: int):
        self.stream = st.stream
        self.rid = st.rid
        self.pos_next = int(st.pos_next)
        self.last0 = int(st.last0)
        self.remaining = int(st.remaining)
        self.step_idx = int(st.step_idx)
        self.temperature = st.temperature
        self.eos0 = st.eos0
        self.step_keys = st.step_keys
        self.n_used = int(n_used)       # exported blocks (written KV)
        self.payload = None             # wire payload once fetched
        self.fetched = False            # tier lookup happened
        self.hibernated_at = time.perf_counter()


class _Prefill:
    """An admitted request's in-progress (possibly chunk-interleaved)
    prefill: blocks are allocated, ``p`` tokens are in the arena."""

    __slots__ = ("req", "blocks", "slot", "p", "t", "logits", "handoff",
                 "moe", "h_last", "marks")

    def __init__(self, req: _Request, blocks: List[int], slot: int,
                 matched_len: int, handoff: Optional[KVHandoff] = None):
        self.req = req
        self.blocks = blocks
        self.slot = slot
        self.p = matched_len            # tokens already in the arena
        self.t = req.prompt0.shape[0]
        self.logits = None
        self.moe = None                 # routed layers' counts, on the device
        self.h_last = None              # self-drafting: the hidden state at p - 1
        self.handoff = handoff          # set: re-prefill, don't re-emit
        # a windowed class's first entry not yet let go (BlockPool.advance)
        self.marks = defaultdict(int)


# ---------------------------------------------------------------------- #
class LMServingEngine:
    """Serve ``TransformerLM`` generation with continuous batching over
    a paged, prefix-shared KV cache.

    Args:
        model: a built ``TransformerLM`` (params are frozen at
            construction, like :class:`ServingEngine`).
        slots: decode batch width S — concurrent in-flight requests.
        cache_len: per-REQUEST context cap (default ``model.max_len``);
            every request needs ``prompt_len + max_new <= cache_len``.
            No longer a per-slot HBM region: KV memory is pooled.
        max_new_tokens: default generation budget per request.
        prefill_buckets: prompt-length pad buckets (default powers of
            two up to ``cache_len``); one AOT prefill executable each.
            Prompts longer than the largest bucket prefill in
            block-aligned chunks of it.
        block_len: tokens per KV block (the page size).
        num_blocks: total pool blocks including the reserved scratch
            block (default: headroom for ``slots`` worst-case requests
            plus a few radix-cached chains).
        enable_prefix_cache: radix prefix sharing on admission
            (default on; sharing never changes streamed tokens).
        temperature: default sampling temperature (0 = greedy, the
            bit-exact-vs-offline path).
        eos_id: default 1-based stop token; generation also stops at
            ``max_new``.
        max_queue: admission queue bound (``ServingQueueFull`` beyond).
        decode_attn: how the decode step reads the paged cache:
            "gather" (the live list's walk, the XLA path),
            "paged_kernel" (the Pallas kernel of the pool's kind) or
            "auto" (default): ``generate.decode_attention_path``
            resolves it once, from the platform and the pool's shapes.
        kv_quant: ``None`` (full-precision KV, the default) or
            ``"int8"``: the block pool stores int8 KV blocks with
            per-(position, head) f32 scales, dequantized inside the
            paged gather — ~4x KV capacity at the same HBM.  Lossy
            (streams are NOT bit-exact vs a full-precision engine);
            forces the gather decode path and excludes disaggregated
            migration (``migrate``/``adopt``).
        spec: optional :class:`~bigdl_tpu.serving.spec.SpecConfig` (or
            an int k) enabling draft-verify speculative decoding: a
            cheap drafter (the target's int8 ``quantize()`` clone by
            default) proposes k tokens per slot and ONE fixed-shape
            donated verify executable scores all k+1 candidates per
            step.  Streams stay bit-exact vs offline generate under the
            default ``"replay"`` acceptance; a per-slot acceptance EMA
            demotes collapsing slots to plain decode and re-probes.
            A model with a prediction module (``TransformerLM(mtp=...)``)
            and no other drafter named drafts for itself through its own
            latent pool: ``SpecConfig(k=1)``, one program a round, no
            drafter arena (``serving.spec``).
        max_prefill_chunk_tokens: Sarathi-style chunked-prefill
            interleaving — when set, the worker advances at most ONE
            block-aligned chunk of at most this many prompt tokens
            between decode rounds, so a long prompt landing mid-decode
            bounds every active stream's inter-token gap at one chunk's
            prefill instead of the whole prompt.  Trades TTFT for ITL;
            streams stay token-identical (chunk boundaries only change
            when KV rows are written, never their values).  Default
            None keeps the run-to-completion admission prefill.
        migrate: marks this engine a PREFILL-PHASE replica: after a
            request's first token is emitted, ``migrate(handoff,
            blocks, pool)`` is called (in the worker thread; the block
            chain stays referenced for the duration of the call) and
            the request leaves this engine — the DisaggCoordinator
            exports the chain and hands it to a decode replica's
            :meth:`adopt`.  Mutually exclusive with ``spec``.
        kvtier: optional
            :class:`~bigdl_tpu.serving.kvtier.HostBlockStore` — the
            host-RAM (+ disk spill) KV tier below the HBM arena.  When
            set, radix-tail eviction DEMOTES unreferenced prefix
            blocks into it instead of dropping them (int8 pools demote
            with their scales), admission PROMOTES any surviving
            host-tier continuation of a matched prefix back into HBM
            (prefilling only past it), and :meth:`hibernate` /
            :meth:`resume` swap whole idle streams out of their decode
            slots and back, bit-exactly.
        metrics / metrics_prefix: inject a shared :class:`LMMetrics`
            (the coordinator aggregates each phase's replicas into one
            per-phase histogram set for the SLO ladders) and/or publish
            under a non-default registry prefix.
    """

    def __init__(self, model, *,
                 slots: int = 8,
                 cache_len: Optional[int] = None,
                 max_new_tokens: int = 32,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 block_len: int = 16,
                 num_blocks: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 max_queue: int = 256,
                 max_cache_entries: int = 16,
                 decode_attn: str = "auto",
                 kv_quant: Optional[str] = None,
                 name: str = "lm",
                 placement=None,
                 tp_rules=None,
                 spec=None,
                 max_prefill_chunk_tokens: Optional[int] = None,
                 migrate=None,
                 kvtier=None,
                 metrics: Optional[LMMetrics] = None,
                 metrics_prefix: str = "serving/lm/"):
        configure_compile_cache()
        import jax
        from bigdl_tpu.models.transformer.generate import (
            _decode_pick_paged, _insert_blocks, _insert_rows, _prefill_parts,
            _prefill_suffix_parts, _selfdraft_step_paged, _tree_commit_paged,
            _tree_verify_step_paged, _verify_step_paged,
            decode_attention_path)
        from bigdl_tpu.quant import dequantize_entry
        from bigdl_tpu.serving.kvcache import state as kvstate

        model._built()
        self.model = model
        #: the model's recurrent layers (``mixer="kda"``: with any, a state
        #: arena rides beside the pool) and its latent ones (``mixer="mla"``:
        #: with any, the pool holds one latent row a position); what carries
        #: (k, v) pairs only is refused HERE, each with its one message
        self._state_layers = len(model.state_layers)
        self._latent_layers = len(model.latent_layers)
        refuse_unsupported(model, spec=spec, migrate=migrate, kvtier=kvtier,
                           kv_quant=kv_quant, placement=placement)
        #: the model's own prediction module drafts (``serving.spec``'s third
        #: drafter): its block's rows are one more layer of the latent arena
        self._selfdraft = drafts_for_itself(model, spec)
        if self._selfdraft and (model.kv_layers or not self._latent_layers):
            raise ValueError(
                "a prediction module drafts through a latent pool: the "
                "model's layers are 'mla' layers")
        if self._latent_layers and model.kv_layers:
            raise ValueError(
                "a plan that mixes 'attention' and 'mla' layers cannot be "
                "served: one pool holds one kind of row")
        self.name = name
        self.placement = placement
        self._params = model.params
        self._buffers = model.buffers
        if placement is not None:
            # TP across the slot: Megatron layer-stacked rules; flash
            # attention does not partition under GSPMD, pin XLA first
            from bigdl_tpu.parallel.tensor_parallel import (
                pin_xla_attention, transformer_lm_tp_rules)
            from bigdl_tpu.serving.placement import shard_params_chunked
            if placement.tp > 1:
                pin_xla_attention(model)
                if tp_rules is None:
                    tp_rules = transformer_lm_tp_rules(placement.mesh)
            rules = tp_rules if tp_rules is not None else (lambda p, l: None)
            self._params = shard_params_chunked(self._params, rules,
                                                placement.mesh)
            rep = placement.replicated()
            self._buffers = jax.tree_util.tree_map(
                lambda b: jax.device_put(b, rep), self._buffers)
        self.slots = int(slots)
        self.cache_len = int(cache_len or model.max_len)
        if self.cache_len > model.max_len:
            raise ValueError(
                f"cache_len ({self.cache_len}) exceeds model.max_len "
                f"({model.max_len})")
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self._max_queue = int(max_queue)

        if prefill_buckets is None:
            prefill_buckets = prefill_bucket_lengths(self.cache_len)
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in prefill_buckets)))
        if self.prefill_buckets[-1] > self.cache_len:
            raise ValueError(
                f"largest prefill bucket ({self.prefill_buckets[-1]}) "
                f"exceeds cache_len ({self.cache_len}): a bucket longer "
                "than the per-request context cap can never fill")

        self.block_len = int(block_len)
        # padded block-table width: every request's chain fits in M ids
        self.table_width = -(-self.cache_len // self.block_len)
        # over-length prompts prefill in block-aligned chunks of the
        # largest bucket; 0 means buckets are sub-block (no chunking)
        self._chunk_full = (self.prefill_buckets[-1]
                            // self.block_len) * self.block_len
        self.migrate = migrate
        self.phase = "prefill" if migrate is not None else "colocated"
        if migrate is not None and spec is not None:
            raise ValueError(
                "a prefill-phase replica (migrate=...) cannot speculate: "
                "it never decodes — speculation belongs on the decode "
                "replicas")
        if kv_quant is not None and migrate is not None:
            raise ValueError(
                "kv_quant='int8' excludes disaggregated serving: the "
                "handoff protocol carries full-precision wire payloads "
                "(the host KV tier, not the coordinator, is the "
                "quantized-chain migration path)")
        self.max_prefill_chunk_tokens = None
        self._chunk_cap = None
        if max_prefill_chunk_tokens is not None:
            if self._chunk_full == 0:
                raise ValueError(
                    "max_prefill_chunk_tokens needs at least one "
                    f"block-aligned prefill bucket (block_len "
                    f"{self.block_len}, largest bucket "
                    f"{self.prefill_buckets[-1]})")
            self.max_prefill_chunk_tokens = int(max_prefill_chunk_tokens)
            # chunk boundaries must stay block-aligned so the suffix
            # prefill's prefix_len is a whole number of blocks
            self._chunk_cap = max(
                self.block_len,
                (self.max_prefill_chunk_tokens
                 // self.block_len) * self.block_len)
        # the pool's geometry is the K/V heads': a model whose query heads
        # share them in groups stores (and moves) the shared heads only,
        # and only its attention layers have an arena layer, a CLASS of
        # blocks a kind of softmax layer (``model.cache_classes``)
        # -- or the latent rows': a model none of whose layers keeps a
        # (k, v) pair gets no (k, v) arena at all
        self._classes = model.cache_classes
        if self._latent_layers:
            classes = [dict(n_layers=self._latent_layers + self._selfdraft,
                            n_heads=1, head_dim=model.mla.row)]
        else:
            classes = [dict(n_layers=len(c.layers), n_heads=c.n_kv,
                            head_dim=c.k_dim, v_dim=c.v_dim, window=c.window)
                       for c in self._classes]
        if not classes:
            raise ValueError("the paged engine needs at least one attention "
                             "or latent attention layer in the model's plan")
        #: the blocks of the largest piece a prefill runs at once
        chunk_blocks = -(-min(self.prefill_buckets[-1],
                              self._chunk_cap or self.prefill_buckets[-1])
                         // self.block_len)
        if not isinstance(num_blocks, (list, tuple)):
            if num_blocks is None:
                # slots worst-case chains + headroom for radix-held prefixes
                num_blocks = 1 + (self.slots + 4) * self.table_width
            # the PRIMARY class's (the first without a window); a class with
            # a window is sized from the slots and the window: what every
            # slot holds of it while it decodes, a chunk in flight, and the
            # prompts' blocks that the prefix cache keeps beside live
            # sequences -- never more than the primary's
            primary = next((i for i, c in enumerate(classes)
                            if c.get("window") is None), 0)
            num_blocks = [
                int(num_blocks) if i == primary else min(
                    int(num_blocks),
                    1 + chunk_blocks + self.slots * (
                        window_blocks(c["window"], self.block_len) + 1
                        + chunk_blocks))
                for i, c in enumerate(classes)]
        if len(num_blocks) != len(classes):
            raise ValueError(f"num_blocks names {len(num_blocks)} classes, "
                             f"the model's plan has {len(classes)}")
        for c, n in zip(classes, num_blocks):
            c["num_blocks"] = int(n)
        self._chunk_blocks = chunk_blocks
        dt = self._params["embed"].dtype
        self.pool = BlockPool(classes=classes, block_len=self.block_len,
                              dtype=dt, kv_quant=kv_quant,
                              latent=bool(self._latent_layers))
        #: entries of a round's live list a class, side by side in one list
        self._live_entries = [
            class_entries(self.slots, self.table_width, c.window,
                          self.block_len) for c in self.pool.classes]
        self._windows = [c.window for c in self.pool.classes]
        #: what a sequence may hold of each windowed class while it decodes
        #: (the window's blocks and the one being written)
        self._window_need = {
            i: min(self.table_width,
                   window_blocks(self.pool.classes[i].window,
                                 self.block_len) + 1)
            for i in self.pool.windowed}
        self.kv_quant = self.pool.kv_quant
        _kvq = self.kv_quant is not None
        if placement is not None:
            # KV arenas live replicated on the slot: every TP device
            # attends over the full (sharded-head math happens on the
            # projections, not the cache) and the donated insert/decode
            # executables keep the committed layout
            _rep = placement.replicated()
            self.pool.arenas = [jax.device_put(a, _rep)
                                for a in self.pool.arenas]
        #: one row a slot and recurrent layer, beside the pool (None: the
        #: model keeps K/V alone)
        self.state = None
        if self._state_layers:
            shapes = model.state_shapes
            self.state = kvstate.StateArena(
                n_layers=self._state_layers, slots=self.slots,
                state_shape=shapes[0], tail_shape=shapes[1], tail_dtype=dt)
            if placement is not None:
                self.state.arenas = [jax.device_put(a, placement.replicated())
                                     for a in self.state.arenas]
            # which form the decode step's recurrence takes, as its trace
            # will ask (the platform and the state's shape): "kernel" / "xla"
            from bigdl_tpu.ops.kda_step import kda_step_path
            self._state_step_path = kda_step_path(*shapes[0])
        # a radix hit hands a request K/V blocks it did not compute; no
        # recurrent state exists at that boundary, so such a model shares
        # no prefix (matched tokens 0 always)
        self.radix = (RadixCache(self.pool)
                      if enable_prefix_cache and not self._state_layers
                      else None)
        self._prefix_cache_note = (
            "on" if self.radix is not None else
            "off: the model has recurrent layers, and a shared prefix's K/V "
            "blocks come without the state at their boundary (snapshots of "
            "state at block boundaries: ROADMAP M6)" if self._state_layers
            else "off: enable_prefix_cache=False")
        #: router-published prefix summary (see attach_radix_summary)
        self.radix_summary = None
        self.kvtier = kvtier
        if self.kvtier is not None and self.radix is not None:
            # THE demote hook: radix-tail eviction hands each victim
            # block to the host tier (with scales, when quantized)
            # instead of dropping it
            self.radix.on_evict = self._demote_block
        self._cache_dtype = dt
        # prefix-chain pad buckets (powers of two up to the table width; a
        # latent layer walks its prefix as far as it reaches, whatever the
        # padding behind it: one bucket, one suffix executable a chunk bucket)
        from bigdl_tpu.models.transformer.generate import walks_prefix
        self._prefix_block_buckets = (
            (self.table_width,)
            if self._latent_layers or walks_prefix(self.table_width,
                                                   self.block_len) else
            prefill_bucket_lengths(self.table_width, min_bucket=1))

        # -- the device programs ---------------------------------------- #
        _ptag = placement.tag if placement is not None else ""
        _out_rep = (placement.replicated()
                    if placement is not None and placement.tp > 1 else None)

        def _constrain(y):
            # TP leaves prefill logits/kv sharded mid-graph; pin every
            # output replicated so the host pull and the (replicated)
            # arena insert see one clean layout
            if _out_rep is None:
                return y
            return jax.lax.with_sharding_constraint(y, _out_rep)

        def _prefill_fn(params, buffers, x):
            del buffers  # part of the CompileCache signature only
            return _constrain(_prefill_parts(model, dequantize_entry(params),
                                             x["ids"], x["len"] - 1,
                                             mtp=self._selfdraft))

        self.prefill_cache = CompileCache(
            _prefill_fn, max_entries=max_cache_entries, placement_tag=_ptag,
            name=f"lm/{name}/prefill")

        def _prefix_prefill_fn(params, buffers, x):
            del buffers
            # a recurrent model's suffix starts from its slot's rows
            carried = (kvstate.read_slot(*x["state"], x["slot"])
                       if "state" in x else ())
            return _constrain(_prefill_suffix_parts(
                model, dequantize_entry(params), x["ids"], x["len"] - 1,
                x["prefix_len"], x["blocks"], *x["kv"], carried=carried,
                h_prev=x.get("h_prev")))

        self.prefix_prefill_cache = CompileCache(
            _prefix_prefill_fn, max_entries=max_cache_entries,
            placement_tag=_ptag, name=f"lm/{name}/prefix_prefill")

        decode_attn = self.decode_attn = decode_attention_path(
            model, self.pool, decode_attn)

        # every step program takes the pool's arenas last, (k, v) or
        # (k, v, ks, vs), donated, and hands them back after its result;
        # the decode step takes the state arenas (state, tail) behind them
        _n_kv = len(self.pool.arenas)

        def _donated(first):
            return tuple(range(first, first + _n_kv))

        def _decode_fn(params, operands, prev_ids, *kv):
            # the step picks its tokens: (S,) ids leave, never (S, V) logits,
            # and come back as the next call's prev_ids without a transfer
            token, pos, temperature, keys, live = split_decode_operands(
                operands, self.slots)
            return _constrain(_decode_pick_paged(
                model, dequantize_entry(params), token, pos, live,
                temperature, keys, prev_ids, *kv,
                table_width=self.table_width, attn_impl=decode_attn))

        self._decode_jit = jax.jit(_decode_fn, donate_argnums=tuple(
            range(3, 3 + len(self._arenas()))))
        self._decode_exec = None
        #: the last decode step's ids, on the device (zeros before the first)
        _ids = np.zeros((self.slots,), np.int32)
        self._ids = (jax.device_put(_ids, placement.replicated())
                     if placement is not None else jax.device_put(_ids))
        #: the decode round on the device that has not been collected
        self._flying: Optional[_Round] = None
        self._step_end = 0.0    # where the last lm/decode_step span ended
        if self.state is not None:
            self._state_insert_jit = jax.jit(kvstate.write_slot,
                                             donate_argnums=(0, 1))
            self._state_insert_exec = None
        #: routed expert layers of the model: with any, the decode step
        #: hands their integers out beside the ids
        self._moe_layers = model.moe_layers
        #: ... and a self-drafting round's: the module's block rides it
        self._round_moe_layers = self._moe_layers + (
            self._selfdraft and model.mtp.mlp == "moe")

        #: a round's live blocks are attended this many at a time
        self._list_chunk = list_chunk(
            self.slots,
            any(s.n_head != model.kv_heads(s) for _, period in model.plan
                for s in period if s.mixer == "attention"),
            bool(self._latent_layers))
        #: self-drafting: a slot's hidden state at its prompt's end, kept
        #: from its prefill for its first round's first pair (S, hidden)
        self._hid = None
        #: ... and the last self-drafting round's (S, 4) output, on the device
        self._prev_out = None
        if self._selfdraft:
            self._hid = jax.device_put(
                np.zeros((self.slots, model.hidden_size), dt))
            self._prev_out = jax.device_put(np.zeros((self.slots, 4), np.int32))

            def _insert_hid(arena, hid, new, block_ids, h_last, slot):
                # the chunk's rows (the module's behind the main layers')
                # and the chunk's last hidden state into its slot's row
                return _insert_rows(arena, new, block_ids) + (
                    jax.lax.dynamic_update_slice(
                        hid, h_last.astype(hid.dtype), (slot, 0)),)

            self._insert_jit = jax.jit(_insert_hid, donate_argnums=(0, 1))
        elif self._latent_layers:
            self._insert_jit = jax.jit(_insert_rows, donate_argnums=(0,))
        elif len(self.pool.classes) > 1:
            _nc, _na = len(self.pool.classes), len(self.pool.classes[0].arenas)

            def _insert_classes(*ops):
                # every class's chunk rows into its own blocks: the arenas
                # (k, v -- and an int8 pool's scales -- a class), the rows
                # (k, v a class), the ids (C, nb)
                kv, new, ids = ops[:_na * _nc], ops[_na * _nc:-1], ops[-1]
                out = ()
                for c in range(_nc):
                    mine = kv[_na * c:_na * (c + 1)]
                    out += tuple(_insert_blocks(
                        *mine[:2], *new[2 * c:2 * c + 2], ids[c], *mine[2:]))
                return out

            self._insert_jit = jax.jit(
                _insert_classes, donate_argnums=tuple(range(_na * _nc)))
        else:
            self._insert_jit = jax.jit(
                _insert_blocks,
                donate_argnums=(0, 1, 5, 6) if _kvq else (0, 1))
        self._insert_execs: dict = {}

        # -- speculation (draft-verify) --------------------------------- #
        self.spec = None
        self.draft = None
        self.spec_metrics = None
        self._verify_jit = None
        self._verify_exec = None
        self._verify_compiles = 0
        if spec is not None:
            from bigdl_tpu.quant import params_dtype_tag, set_compute_mode
            from bigdl_tpu.serving.spec import (DraftModel, NgramDrafter,
                                                SpecConfig, SpecMetrics)
            if isinstance(spec, int):
                spec = SpecConfig(k=spec)
            self.spec = spec
            draft_lm = spec.draft
            if self._selfdraft:
                # the model's own prediction module, through the target's
                # pool: no second model, no arena, no programs of its own
                from bigdl_tpu.serving.spec import SelfDrafter
                self.draft = SelfDrafter(model,
                                         arena_layer=self._latent_layers)
            elif getattr(spec, "drafter_compute", None) == "ngram":
                # zero-model prompt-lookup drafter: host-side suffix
                # matching, no device programs, no arena
                self.draft = NgramDrafter(
                    model.vocab_size, slots=self.slots,
                    ngram_max=spec.ngram_max)
            elif draft_lm is None:
                # derive the default drafter: the target's int8 clone
                # (or the target itself when it is already quantized),
                # running the kernels spec.drafter_compute asks for —
                # drafter numerics only move the acceptance rate, the
                # emitted stream is the target's under "replay"
                comp = getattr(spec, "drafter_compute", "dequant")
                if params_dtype_tag(model.params) == "int8":
                    draft_lm = model
                    if comp != "dequant":
                        # aux-only rewrite: the clone shares every int8
                        # buffer with the target, only the compute tag
                        # (pytree aux) differs
                        draft_lm = model.clone_module()
                        draft_lm.params = set_compute_mode(
                            model.params, comp)
                        draft_lm.grad_params = None
                        draft_lm = draft_lm.evaluate()
                else:
                    draft_lm = model.quantize("int8", compute=comp)
            if self.draft is None:
                if draft_lm.vocab_size != model.vocab_size:
                    raise ValueError(
                        f"draft model vocab ({draft_lm.vocab_size}) "
                        f"differs from the target's ({model.vocab_size}): "
                        "drafted token ids would not be the target's "
                        "token ids")
                self.draft = DraftModel(
                    draft_lm, slots=self.slots, cache_len=self.cache_len,
                    prefill_buckets=self.prefill_buckets,
                    max_cache_entries=max_cache_entries,
                    sampling=spec.sampling, placement_tag=_ptag)
            self.spec_metrics = SpecMetrics().publish_to(get_registry())
            self.spec_metrics.compute_mode = self.draft.compute_mode
            _drep = getattr(draft_lm, "quant_report", None) or {}
            self.spec_metrics.overflow_risk = float(
                _drep.get("overflow_risk") or 0.0)

            def _verify_fn(params, tokens, pos, n_cand, tables, *kv):
                return _constrain(_verify_step_paged(
                    model, dequantize_entry(params), tokens, pos,
                    n_cand, tables, *kv))

            def _selfdraft_fn(params, operands, hid, prev, *kv):
                # verify, pick and draft in one program: (S, 4) ids and
                # counts leave, never logits, and come back as the next
                # call's prev without a transfer
                (tokens, pos, n_cand, fresh, temperature, keys,
                 live) = split_selfdraft_operands(operands, self.slots, prev)
                return _constrain(_selfdraft_step_paged(
                    model, dequantize_entry(params), tokens, pos, n_cand,
                    fresh, temperature, keys, hid, live, *kv,
                    table_width=self.table_width, attn_impl=decode_attn))

            self._verify_jit = (
                jax.jit(_selfdraft_fn, donate_argnums=_donated(4))
                if self._selfdraft else
                jax.jit(_verify_fn, donate_argnums=_donated(5)))

            if spec.tree:
                # one donated verify executable per ladder rung: the
                # shape's depths/ancestor matrix are static constants of
                # each trace, so mixed-rung rounds ride the round's
                # widest rung with per-slot n_cand truncation (every
                # lower rung is a prefix of it)
                self._tree_shapes = list(spec.shapes)

                def _mk_tree_verify(shp):
                    _depths = np.asarray(shp.depths, np.int32)
                    _anc = np.ascontiguousarray(shp.anc)

                    def _fn(params, tokens, pos, n_cand, tables, *kv):
                        return _constrain(_tree_verify_step_paged(
                            model, dequantize_entry(params), tokens,
                            pos, n_cand, tables, *kv,
                            depths=_depths, anc=_anc))

                    return jax.jit(_fn, donate_argnums=_donated(5))

                self._verify_tree_jits = [
                    _mk_tree_verify(s) for s in self._tree_shapes]
                self._verify_tree_execs: dict = {}
                # the accepted-path commit: only needed when a shape has
                # off-spine nodes, sized to the deepest alternate depth
                self._commit_dmax = max(
                    (s.max_depth for s in self._tree_shapes
                     if not s.is_chain), default=0)
                def _commit_fn(src, pos, tables, *kv):
                    return _constrain(_tree_commit_paged(
                        src, pos, tables, *kv, n_heads=self.pool.n_heads))

                self._commit_jit = jax.jit(_commit_fn,
                                           donate_argnums=_donated(3))
                self._commit_exec = None
                self._commit_compiles = 0

        self.metrics = (metrics if metrics is not None
                        else LMMetrics(self.slots)).publish_to(
            get_registry(), prefix=metrics_prefix)
        self.metrics.spec = self.spec_metrics
        if self.state is not None:
            self.metrics.state_bytes = self.state.arena_bytes
        if self.pool.latent:
            self.metrics.latent_row_bytes = self.pool.row_bytes
        self._publish_kv_metrics(get_registry())

        # memory-ledger attribution: KV arenas (+ int8 scale arenas),
        # staged params per placement slot, and the drafter's dense
        # arena.  Providers are weakref'd — a closed, collected engine's
        # bytes drop out of the table instead of pinning the arrays.
        self._ledger_keys: List[tuple] = []
        try:
            import weakref as _weakref

            from bigdl_tpu.obs.ledger import get_ledger
            from bigdl_tpu.quant import params_dtype_tag, params_nbytes
            led = get_ledger()
            _dev = placement.tag if placement is not None else None
            _pool_ref = _weakref.ref(self.pool)

            def _kv_bytes():
                p = _pool_ref()
                return p.kv_arena_bytes if p is not None else None

            self._ledger_keys.append(led.register(
                "kvcache", f"{name}/kv_arena", _kv_bytes,
                shape=self.pool.shape, dtype=str(self.pool.dtype),
                device=_dev))
            if self.kv_quant is not None:
                def _scale_bytes():
                    p = _pool_ref()
                    return (p.scale_arena_bytes if p is not None
                            else None)

                self._ledger_keys.append(led.register(
                    "kvcache", f"{name}/scale_arena", _scale_bytes,
                    shape=self.pool.scale_shape, dtype="float32",
                    device=_dev))
            if self.state is not None:
                _state_ref = _weakref.ref(self.state)

                def _state_bytes():
                    a = _state_ref()
                    return a.arena_bytes if a is not None else None

                self._ledger_keys.append(led.register(
                    "kvcache", f"{name}/state_arena", _state_bytes,
                    shape=self.state.state.shape, dtype="float32",
                    device=_dev))
            self._ledger_keys.append(led.register(
                "params", f"{name}/staged",
                params_nbytes(self._params), device=_dev,
                note=f"quant={params_dtype_tag(self._params)}"))
            if self.draft is not None and \
                    getattr(self.draft, "k", None) is not None:
                # (the n-gram drafter has no arena — nothing to attribute)
                _draft_ref = _weakref.ref(self.draft)

                def _draft_bytes():
                    d = _draft_ref()
                    return d.arena_bytes if d is not None else None

                self._ledger_keys.append(led.register(
                    "spec", f"{name}/draft_arena", _draft_bytes,
                    shape=self.draft.k.shape,
                    dtype=str(self.draft.k.dtype), device=_dev))
        except Exception:
            log.exception("memory-ledger registration failed")

        # -- scheduler state (worker thread owns the slots) ------------- #
        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._adopt_q: deque = deque()       # pending KVHandoff adoptions
        self._prefilling: deque = deque()    # chunk-interleaved _Prefills
        self._prefill_since_step = False     # splits the ITL histograms
        self.migrated = 0       # prefill phase: chains handed off
        self.adopted = 0        # decode phase: chains seated
        self.re_prefills = 0    # decode phase: lost payloads recomputed
        # -- session hibernation (host KV tier) ------------------------- #
        self._hibernate_req: set = set()     # rids awaiting swap-out
        self._hibernated: dict = {}          # rid -> _Hibernated
        self._resume_q: deque = deque()      # _Hibernated awaiting seats
        self.hibernations = 0   # streams swapped out to the host tier
        self.resumes = 0        # streams seated back from hibernation
        self.resume_re_prefills = 0  # lost payloads rebuilt via replay
        # the SLO controller's decode-concurrency actuator: the decode
        # executable always steps the full S physical slots (fixed
        # shape — no recompile), but admission only fills slots up to
        # this cap, trading throughput for per-token latency live
        self._slot_limit = self.slots
        self._free = list(range(self.slots))
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._n_active = 0
        self._closing = False
        self._abort = False
        self._lc_nudge = False    # a cancel/deadline wants a sweep
        # -- request lifecycle (deadlines / cooperative cancel) ---------- #
        self._lc_lock = threading.Lock()
        self.lifecycle = {
            "expired_preadmission": 0,   # shed before prefill
            "expired_midstream": 0,      # truncated while decoding
            "cancelled": 0,              # cooperative cancels honored
            "wasted_decode_steps": 0,    # slot-steps past cancel/deadline
        }
        _reg = get_registry()
        self._lc_counters = {
            k: _reg.counter(f"serving/lifecycle/{k}")
            for k in self.lifecycle}
        # -- phase stamps: the worker thread's own timeline -------------- #
        # one perf_counter() read at each phase boundary (_stamp) feeds
        # the round record in LMMetrics always and, with the tracer on,
        # the lm/* phase spans and the profiler's host-plane annotations
        self._ph = P_SCHED                  # the leaf phase now open
        self._ph_t0 = time.perf_counter()   # ... and since when
        self._ph_args = None                # extra args of its span
        self._ph_ann = None                 # its live TraceAnnotation
        self._adm_rid = None                # sampled request being admitted
        self._adm_note = None               # lm/admit args from its callees
        self._rd = [0.0] * len(ROUND_PHASES)    # this round's split
        # the device's account: nothing is enqueued yet, so the device is
        # empty; what of a round's phases passes while it is (lm/idle
        # aside) is the round's starved split, None while nothing did
        self._dev = _DEV_EMPTY
        self._sv = None
        self._sv_t0 = self._ph_t0               # since when proven empty
        self._sv_after = "start"                # ... and by what
        self._rd_t0 = self._ph_t0
        self._rd_index = 0
        self._rd_active = 0
        # round-cadence stall detection, wired as ServingEngine wires its
        # dispatch: a round past k medians dumps every thread's stack
        # WHILE it hangs; the round record sizes its phases afterwards
        self.watchdog = (shared_watchdog("lm_round")
                         .reset(**env_watchdog_kwargs())
                         if env_watchdog_enabled() else None)
        self._worker = threading.Thread(
            target=self._run, daemon=True, name=f"lm-serve-{name}")
        self._worker.start()
        # flight-recorder hookup: incident bundles capture the engine's
        # scheduler/kv state and the active request ids.  weakref'd so
        # a closed engine is collectable.
        try:
            from bigdl_tpu.obs import flight
            import weakref
            ref = weakref.ref(self)

            def _flight_state():
                eng = ref()
                return eng.stats() if eng is not None else None

            def _flight_requests():
                eng = ref()
                if eng is None:
                    return []
                with eng._cv:
                    rids = [r.rid for r in eng._queue]
                    rids += [st.rid for st in eng._slots
                             if st is not None]
                return rids

            flight.register_state(f"lm_engine/{name}", _flight_state)
            flight.register_requests(f"lm_engine/{name}",
                                     _flight_requests)
        except Exception:
            log.exception("flight-recorder registration failed")

    def _publish_kv_metrics(self, registry) -> None:
        # the process-wide registry outlives the engine: its gauges read
        # through a weakref, or a closed engine (and the model's weights
        # behind it) could never be collected
        import weakref
        ref = weakref.ref(self)

        def gauge(read):
            def fn():
                eng = ref()
                return None if eng is None else read(eng)
            return FnGauge(fn)

        registry.register("kvcache/block_utilization",
                          gauge(lambda e: e.pool.utilization()),
                          replace=True)
        registry.register(
            "kvcache/prefix_hit_rate",
            gauge(lambda e: e.radix.hit_rate()
                  if e.radix is not None else None),
            replace=True)
        registry.register(
            "kvcache/prefill_tokens_saved",
            gauge(lambda e: e.radix.matched_tokens
                  if e.radix is not None else 0),
            replace=True)
        registry.register(
            "kvcache/evictions",
            gauge(lambda e: e.radix.evictions
                  if e.radix is not None else 0),
            replace=True)
        registry.gauge("kvcache/arena_bytes",
                       unit="bytes").set(self.pool.arena_bytes)

    # ------------------------------------------------------------------ #
    def warmup(self) -> int:
        """AOT-compile every prefill bucket plus the decode and insert
        executables before traffic (ONE decode executable: it reads a
        live list of any length up to whole tables); returns the number
        of prefill executables compiled.  Warmup never executes on the
        resident arenas (it lowers against shapes), so it is safe
        mid-traffic."""
        import numpy as _np

        inputs = [{"ids": _np.zeros((1, b), _np.int32),
                   "len": _np.int32(b)} for b in self.prefill_buckets]
        n = self.prefill_cache.warmup_inputs(
            self._params, self._buffers, inputs)
        if self.draft is not None:
            # a spec engine decodes through the verify executable (a
            # plain-decode slot is just an n_cand=1 row); the drafter
            # warms its own prefill/decode/insert programs
            if self.spec.tree:
                # tree mode: one executable per ladder rung, plus the
                # accepted-path commit when any shape has alternates
                for r in range(len(self._tree_shapes)):
                    self._verify_tree_compiled(r)
                if self._commit_dmax:
                    self._commit_compiled()
            else:
                self._verify_compiled()
            self.draft.warmup()
        elif self.migrate is None:
            # a prefill-phase replica never decodes — its requests
            # migrate after the first token — so skip that compile
            self._decode_compiled()
        for b in self.prefill_buckets:
            self._insert_compiled(b)
        if self.state is not None:
            self._state_insert_compiled()
        return n

    def warmup_prefix(self, suffix_lens: Optional[Sequence[int]] = None,
                      prefix_blocks: Optional[Sequence[int]] = None) -> int:
        """AOT-compile the prefix-suffix prefill executables: one per
        (suffix bucket, prefix-chain bucket) pair.  Optional — they
        also compile on first use — but a TTFT-sensitive deployment
        warms them so the first shared-prefix hit doesn't pay a
        compile.  Pass the expected unmatched-suffix lengths and cached
        prefix block counts to warm only the combinations the traffic
        will hit (the full cross product otherwise).  Returns the
        number newly compiled."""
        import numpy as _np

        if suffix_lens is not None:
            cap = self.prefill_buckets[-1]
            sb = sorted({self.bucket_for(min(int(s), cap))
                         for s in suffix_lens})
        else:
            sb = list(self.prefill_buckets)
        if prefix_blocks is not None:
            pbs = sorted({self._prefix_bucket_for(int(p))
                          for p in prefix_blocks})
        else:
            pbs = list(self._prefix_block_buckets)
        inputs = []
        for b in sb:
            for pb in pbs:
                x = {"ids": _np.zeros((1, b), _np.int32),
                     "len": _np.int32(b),
                     "prefix_len": _np.int32(pb * self.block_len),
                     "blocks": self._by_class(_np.zeros(
                         (len(self.pool.classes), pb), _np.int32)),
                     "kv": self.pool.arenas, **self._carried_operands(0),
                     **self._h_prev_operand(None)}
                inputs.append(x)
        return self.prefix_prefill_cache.warmup_inputs(
            self._params, self._buffers, inputs)

    def _by_class(self, rows):
        """A class a row ``(C, ..)`` as the step programs take it: the one
        row itself where the pool has one class."""
        return rows if len(self.pool.classes) > 1 else rows[0]

    def _tables_shape(self) -> tuple:
        one = (self.slots, self.table_width)
        n = len(self.pool.classes)
        return (n,) + one if n > 1 else one

    def _arenas(self) -> tuple:
        """What the decode step takes last, donated, and hands back: the
        pool's arenas and, behind them, the state arenas."""
        return tuple(self.pool.arenas) + (
            self.state.arenas if self.state is not None else ())

    def _carried_operands(self, slot: int) -> dict:
        """What a suffix prefill of a recurrent model takes beside the
        pool: the state arenas and the slot whose rows it starts from."""
        if self.state is None:
            return {}
        return {"state": self.state.arenas, "slot": np.int32(slot)}

    def _h_prev_operand(self, h_prev) -> dict:
        """What a suffix prefill of a self-drafting engine takes beside the
        pool: the hidden state before the chunk (zeros where none is known:
        a warm-up's shapes, the pass that computes one)."""
        if not self._selfdraft:
            return {}
        if h_prev is None:
            h_prev = np.zeros((1, self.model.hidden_size), self._cache_dtype)
        return {"h_prev": h_prev}

    def _state_insert_compiled(self):
        """The program that writes one slot's rows of the state arenas
        (``kvcache.state.write_slot``, both donated)."""
        if self._state_insert_exec is None:
            import jax
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            sds = jax.ShapeDtypeStruct
            state, tail = self.state.arenas
            one = lambda a: sds(a.shape[:1] + (1,) + a.shape[2:],   # noqa: E731
                                a.dtype, **sh)
            self._state_insert_exec = self._state_insert_jit.lower(
                state, tail, one(state), one(tail),
                sds((), np.int32, **sh)).compile()
            self._ledger_exec("state_insert", f"slots={self.slots}",
                              self._state_insert_exec)
        return self._state_insert_exec

    def _decode_compiled(self):
        if self._decode_exec is None:
            import jax
            # under placement the scheduler's np operands must lower as
            # slot-replicated (an unannotated lowering would bake in the
            # default device, clashing with the slot-committed params);
            # Compiled.__call__ auto-places the uncommitted np arrays
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            # one operand a round, the live list in it at the one length
            # that holds any round's
            ops = decode_operands(self.slots, sum(self._live_entries))[0]
            self._decode_exec = self._decode_jit.lower(
                self._params, jax.ShapeDtypeStruct(ops.shape, ops.dtype, **sh),
                jax.ShapeDtypeStruct(self._ids.shape, self._ids.dtype, **sh),
                *self._arenas()).compile()
            self._ledger_exec("decode", f"slots={self.slots}",
                              self._decode_exec)
        return self._decode_exec

    def _verify_compiled(self):
        """The spec engine's single verify executable: all S slots, all
        W = k+1 candidate rows, every round — k is static per engine
        and slots pad with n_cand, so like decode this lowers ONCE."""
        if self._verify_exec is None and self._selfdraft:
            import jax
            # the self-drafting round: one operand vector, the hidden rows
            ops = selfdraft_operands(self.slots,
                                     self.slots * self.table_width)[0]
            self._verify_exec = self._verify_jit.lower(
                self._params, jax.ShapeDtypeStruct(ops.shape, ops.dtype),
                self._hid, self._prev_out, *self.pool.arenas).compile()
            self._verify_compiles += 1
            self._ledger_exec("verify", f"slots={self.slots}/selfdraft",
                              self._verify_exec)
        if self._verify_exec is None:
            import jax
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            sds = jax.ShapeDtypeStruct
            w = self.spec.k + 1
            tok = sds((self.slots, w), np.int32, **sh)
            pos = sds((self.slots,), np.int32, **sh)
            ncand = sds((self.slots,), np.int32, **sh)
            tables = sds(self._tables_shape(), np.int32, **sh)
            self._verify_exec = self._verify_jit.lower(
                self._params, tok, pos, ncand, tables,
                *self.pool.arenas).compile()
            self._verify_compiles += 1
            self._ledger_exec("verify", f"slots={self.slots}",
                              self._verify_exec)
        return self._verify_exec

    def _verify_tree_compiled(self, rung: int):
        """Tree mode's bounded-executables contract: one donated verify
        per ladder rung (the shape's mask/depths are trace constants),
        counted in ``_verify_compiles`` exactly like linear verify.  A
        round lowers at its widest participating rung; narrower slots
        truncate with ``n_cand``."""
        exe = self._verify_tree_execs.get(rung)
        if exe is None:
            import jax
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            sds = jax.ShapeDtypeStruct
            w = self._tree_shapes[rung].width
            tok = sds((self.slots, w), np.int32, **sh)
            pos = sds((self.slots,), np.int32, **sh)
            ncand = sds((self.slots,), np.int32, **sh)
            tables = sds((self.slots, self.table_width), np.int32, **sh)
            exe = self._verify_tree_jits[rung].lower(
                self._params, tok, pos, ncand, tables,
                *self.pool.arenas).compile()
            self._verify_tree_execs[rung] = exe
            self._verify_compiles += 1
            self._ledger_exec(
                "verify", f"slots={self.slots}/tree_w={w}", exe)
        return exe

    def _commit_compiled(self):
        """The accepted-path commit executable (tree mode, shapes with
        alternates only): copies each accepted off-spine node's k/v row
        from its store offset to its position offset.  One lowering —
        ``src`` is always (S, Dmax) with identity rows for slots that
        stayed on the spine."""
        if self._commit_exec is None:
            import jax
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            sds = jax.ShapeDtypeStruct
            src = sds((self.slots, self._commit_dmax), np.int32, **sh)
            pos = sds((self.slots,), np.int32, **sh)
            tables = sds((self.slots, self.table_width), np.int32, **sh)
            self._commit_exec = self._commit_jit.lower(
                src, pos, tables, *self.pool.arenas).compile()
            self._commit_compiles += 1
            self._ledger_exec(
                "verify", f"slots={self.slots}/tree_commit",
                self._commit_exec)
        return self._commit_exec

    def _insert_compiled(self, bucket: int):
        exe = self._insert_execs.get(bucket)
        if exe is None:
            import jax
            L, H, B, D = self.pool.wire_shape
            nb = -(-bucket // B)
            sds = jax.ShapeDtypeStruct
            sh = (dict(sharding=self.placement.replicated())
                  if self.placement is not None else {})
            # fresh chunk rows arrive in the model's compute dtype even
            # when the pool stores int8 (_insert_blocks quantizes them); a
            # latent pool's have no head axis
            new = sds((L, 1, bucket, D) if self.pool.latent
                      else (L, 1, H, bucket, D), self._cache_dtype, **sh)
            vnew = new if self.pool.latent else sds(
                (L, 1, H, bucket, self.pool.classes[0].v_dim),
                self._cache_dtype, **sh)
            kv, n = self.pool.arenas, self.pool.data_arenas
            if self._selfdraft:
                exe = self._insert_jit.lower(
                    kv[0], self._hid, new, sds((nb,), np.int32),
                    sds((1, self.model.hidden_size), self._cache_dtype),
                    sds((), np.int32)).compile()
            elif len(self.pool.classes) > 1:
                # a class's chunk rows: its layers, K/V heads and widths
                rows = [sds((c.n_layers, 1, c.n_heads, bucket, d),
                            self._cache_dtype, **sh)
                        for c in self.pool.classes
                        for d in (c.head_dim, c.v_dim)]
                exe = self._insert_jit.lower(
                    *kv, *rows, sds((len(self.pool.classes), nb), np.int32,
                                    **sh)).compile()
            else:
                exe = self._insert_jit.lower(
                    *kv[:n], *[new, vnew][:n], sds((nb,), np.int32, **sh),
                    *kv[n:]).compile()
            self._insert_execs[bucket] = exe
            self._ledger_exec("insert", f"bucket={bucket}", exe)
        return exe

    def _ledger_exec(self, which: str, key: str, exe) -> None:
        """File a directly-lowered executable's cost/memory row with
        the memory ledger (best effort — never breaks a compile)."""
        try:
            from bigdl_tpu.obs.ledger import get_ledger
            get_ledger().record_compiled(f"lm/{self.name}/{which}", key,
                                         exe)
        except Exception:
            pass

    # ------------------------------------------------------------------ #
    def bucket_for(self, prompt_len: int) -> int:
        """Smallest configured prefill bucket >= prompt_len."""
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest prefill "
            f"bucket ({self.prefill_buckets[-1]}) and the buckets are "
            f"smaller than one KV block ({self.block_len}): chunked "
            "prefill needs at least one block-aligned bucket")

    def _prefix_bucket_for(self, n_blocks: int) -> int:
        for pb in self._prefix_block_buckets:
            if pb >= n_blocks:
                return pb
        return self._prefix_block_buckets[-1]

    def submit(self, prompt_ids, *,
               max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               rng=None) -> LMStream:
        """Enqueue one prompt ((t,) or (1, t), 1-based ids); returns an
        :class:`LMStream` of its continuation.

        ``deadline_s`` is an optional wall-clock budget minted here, at
        enqueue: a request still queued when it expires is shed before
        prefill with :class:`ServingDeadlineExceeded`; a stream past it
        mid-decode is finished with a typed truncation marker and its
        slot/blocks recycled the same scheduler round."""
        prompt = np.asarray(prompt_ids).reshape(-1).astype(np.int32)
        t = prompt.shape[0]
        if t == 0:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        if max_new <= 0:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if t + max_new > self.cache_len:
            raise ValueError(
                f"prompt ({t}) + max_new ({max_new}) exceeds cache_len "
                f"({self.cache_len})")
        # the typed whole-pool rejection: a request that could NEVER be
        # satisfied (its total block need exceeds the pool) is shed at
        # admission and counted; anything smaller is admissible — pool
        # pressure merely defers it until streams free blocks
        need = self.pool.blocks_for(t + max_new)
        for i, c in enumerate(self.pool.classes):
            # a class with a window needs a window's blocks and a prefill
            # chunk's, whatever the prompt's length
            mine = need if c.window is None else self._window_claim(t, need)[i]
            if mine > c.capacity:
                self.metrics.record_reject()
                count_rejection()
                raise RequestExceedsPool(
                    f"request needs {mine} KV blocks ({t} prompt + {max_new} "
                    f"new tokens at block_len {self.block_len}); the whole "
                    f"pool holds {c.capacity}"
                    + (f" (class {i}, window {c.window})"
                       if len(self.pool.classes) > 1 else ""))
        if self._chunk_full == 0:
            self.bucket_for(t)  # sub-block buckets: no chunked prefill
        temp = float(self.temperature if temperature is None
                     else temperature)
        eos = eos_id if eos_id is not None else self.eos_id
        eos0 = (int(eos) - 1) if eos is not None else None

        first_key = step_keys = None
        if temp > 0.0:
            # replicate offline generate()'s key chain exactly: one
            # split for the first token, then max_new-1 scan keys
            import jax
            if rng is None:
                rng = jax.random.PRNGKey(0)
            elif isinstance(rng, int):
                rng = jax.random.PRNGKey(rng)
            rng, sub = jax.random.split(rng)
            first_key = np.asarray(sub)
            if max_new > 1:
                step_keys = np.asarray(jax.random.split(rng, max_new - 1))

        # chaos hook on the admission path (same contract as the
        # batcher's): an injected transient surfaces as the typed shed
        from bigdl_tpu.resilience.faults import fault_point
        try:
            fault_point("serving.enqueue", name=self.name, n=t)
        except ServingOverloaded:
            raise
        except TransientBackendError as e:
            self.metrics.record_reject()
            count_rejection()
            raise ServingOverloaded(
                f"admission shed (injected at serving.enqueue): {e}") from e

        rid = mint_request_id()
        stream = LMStream(prompt, max_new, request_id=rid,
                          deadline_s=deadline_s)
        stream._wake_cb = self._lc_wake
        if deadline_s is not None and float(deadline_s) <= 0.0:
            # already dead on arrival: shed synchronously, typed
            self.metrics.record_reject()
            count_rejection()
            self._lc_count("expired_preadmission")
            raise ServingDeadlineExceeded(
                f"deadline_s={deadline_s} already expired at enqueue")
        req = _Request(stream, prompt - 1, max_new, temp, eos0,
                       first_key, step_keys, rid)
        with self._cv:
            if self._closing:
                raise ServingClosed("LMServingEngine is closed")
            if len(self._queue) >= self._max_queue:
                self.metrics.record_reject()
                count_rejection()
                raise ServingQueueFull(
                    f"admission queue full ({self._max_queue})")
            self._queue.append(req)
            depth = len(self._queue)
            self._cv.notify_all()
        self.metrics.record_submit()
        if _tracer.sampled(rid):
            _tracer.instant("lm/enqueue", cat="serve", request_id=rid,
                            prompt_len=t, max_new=max_new,
                            queue_depth=depth)
        return stream

    def adopt(self, handoff: KVHandoff) -> None:
        """Accept a migrated request (decode-phase entry point): the
        handoff's KV chain — transferred wire payload plus whatever the
        local radix already held — is seated into a slot by the worker
        and decode continues from the token the prefill replica already
        emitted.  Adoptions outrank queued submissions (they are
        further along: TTFT is already paid) and defer under pool
        pressure exactly like admissions."""
        refuse_unsupported(self.model, adopt=True)
        # the deadline rides the handoff on the stream itself; rebind
        # the cancel nudge so a disconnect now wakes THIS worker
        handoff.stream._wake_cb = self._lc_wake
        with self._cv:
            if self._closing:
                raise ServingClosed("LMServingEngine is closed")
            self._adopt_q.append(handoff)
            self._cv.notify_all()
        self.metrics.record_submit()
        if _tracer.sampled(handoff.rid):
            _tracer.instant("lm/adopt_enqueue", cat="serve",
                            request_id=handoff.rid,
                            src=handoff.src_name,
                            wire_blocks=(handoff.payload["blocks"]
                                         if handoff.payload else None),
                            matched_blocks=len(handoff.matched))

    # -- live control knobs (the SLO controller's actuators) ----------- #
    def set_slot_limit(self, n: int) -> int:
        """Cap decode concurrency at ``n`` of the S physical slots
        (clamped to [1, slots]).  Cheap: the fixed-shape decode
        executable is untouched; only admission stops filling slots
        beyond the cap.  In-flight requests above a lowered cap finish
        normally — the cap applies to new admissions.  Returns the
        applied value."""
        with self._cv:
            self._slot_limit = max(1, min(int(n), self.slots))
            self._cv.notify_all()
            return self._slot_limit

    @property
    def slot_limit(self) -> int:
        with self._cv:
            return self._slot_limit

    def set_max_queue(self, n: int) -> None:
        """Admission-control actuator: rebind the queue bound live
        (shed new arrivals with ServingQueueFull beyond it); queued
        requests are never dropped."""
        with self._cv:
            self._max_queue = max(0, int(n))

    @property
    def max_queue(self) -> int:
        with self._cv:
            return self._max_queue

    def generate(self, prompt_ids, *,
                 timeout: Optional[float] = None, **kw) -> np.ndarray:
        """Sync convenience: submit + wait; returns (t + generated,)
        1-based ids for one prompt."""
        return self.submit(prompt_ids, **kw).result(timeout=timeout)

    # -- sampling (replicating offline generate exactly) -------------- #
    # A plain decode round picks on the device (``generate.pick_rows``,
    # inside the one decode executable); the host's rule below is its
    # twin, for the rows that do reach the host: an admission's first
    # token and the verify rows.
    @staticmethod
    def _pick(logits_row: np.ndarray, temperature: float, key,
              clamp: bool) -> int:
        # one shared implementation with the speculative acceptance
        # path (spec/verify.py), so the first token, verify rows, and the
        # Gumbel-coupled drafter can never drift apart
        from bigdl_tpu.serving.spec.verify import pick_token
        return pick_token(logits_row, temperature, key, clamp)

    # -- worker -------------------------------------------------------- #
    def _stamp(self, nxt: int) -> float:
        """A phase boundary of the worker thread: one clock read closes
        the open leaf phase into this round's split and opens ``nxt``.
        With the tracer on, the closed phase is also written as a span
        (``lm/<phase>``, carrying its round's index) and ``nxt`` entered
        as a profiler annotation of the same name."""
        now = time.perf_counter()
        self._rd[self._ph] += now - self._ph_t0
        if _tracer.enabled or self._ph_ann is not None:
            self._trace_phase(now, nxt)
        if self._dev:           # (not while a round runs ahead)
            self._account(now, nxt)
        self._ph = nxt
        self._ph_t0 = now
        return now

    def _account(self, now: float, nxt: int) -> None:
        """The device's account at a stamp, while the device is not known
        to hold work.  Proven empty: the phase that closes was STARVED
        unless it was ``lm/idle`` (the engine had work, the device none),
        and an enqueue phase ends the interval -- one ``lm/starved``
        envelope, tracer on.  Proven empty only up to an output with more
        enqueued behind it (an admission's logits, the insert behind
        them): one ``is_ready()`` on the newest output, no wait."""
        ph = self._ph
        if self._dev == _DEV_POLL:
            # nothing is proven at a stamp that opens an enqueue phase: an
            # envelope would hold that one leaf
            if ph in _ENQUEUES:
                self._dev = _DEV_BUSY
            elif nxt not in _ENQUEUES and self._newest_output().is_ready():
                self._proved_empty(
                    now, "idle" if ph == P_IDLE else "first_token")
            return
        if ph == P_IDLE:            # a request woke an idle engine
            self._trace_starved_tail()
            self._sv_t0, self._sv_after = now, "idle"
            return
        if self._sv is None:
            self._sv = [0.0] * len(ROUND_PHASES)
        self._sv[ph] += now - self._ph_t0
        if ph in _ENQUEUES:
            self._dev = _DEV_BUSY
            if _tracer.enabled:
                self._trace_starved(now, ROUND_PHASES[ph])

    def _proved_empty(self, now: float, after: str) -> None:
        """In-order execution: the wait that ended at ``now`` was for the
        newest enqueued output, so nothing is left on the device."""
        self._dev, self._sv_t0, self._sv_after = _DEV_EMPTY, now, after

    def _newest_output(self):
        """An output of the program enqueued last: the arenas every step
        and insert hands back (a recurrent model's state insert follows
        its block insert)."""
        return (self.pool if self.state is None else self.state).arenas[-1]

    def _trace_starved_tail(self) -> None:
        """What the last round left between its drain and ``lm/idle`` (or
        the worker's end) was starved with no enqueue to end it."""
        if (self._dev == _DEV_EMPTY and _tracer.enabled
                and self._ph_t0 > self._sv_t0):
            self._trace_starved(self._ph_t0, "idle")

    def _trace_starved(self, end: float, until: str) -> None:
        _tracer.add_complete(
            "lm/starved", self._sv_t0, end - self._sv_t0, cat="serve",
            args={"round": self._rd_index, "after": self._sv_after,
                  "until": until, "admitted": self._adm_note is not None})

    def _annotate(self, phase: Optional[int]) -> None:
        """Leave the live profiler annotation and, with the tracer on,
        enter ``phase``'s."""
        if self._ph_ann is not None:
            self._ph_ann.__exit__(None, None, None)
        self._ph_ann = (None if phase is None
                        else _tracer.annotation(_PHASE_SPANS[phase]))

    def _trace_phase(self, now: float, nxt: int) -> None:
        self._annotate(nxt)
        args, self._ph_args = self._ph_args, None
        if not _tracer.enabled:
            return
        args = dict(args or (), round=self._rd_index)
        if self._adm_rid is not None:
            args["request_id"] = self._adm_rid
        _tracer.add_complete(_PHASE_SPANS[self._ph], self._ph_t0,
                             now - self._ph_t0, cat="serve", args=args)

    def _nothing_to_do(self) -> bool:
        """Caller holds ``_cv``."""
        return (not self._queue and not self._adopt_q
                and not self._resume_q
                and not self._n_active and not self._prefilling
                and self._flying is None
                and not self._closing and not self._abort
                and not self._lc_nudge)

    def _round_end(self, admitted: int) -> None:
        """Close the round: its ``lm/round`` span (tracer on), its fold
        into the round record, the slow-round line, the watchdog."""
        now = self._stamp(P_SCHED)      # the next round starts here
        t0, index, active = self._rd_t0, self._rd_index, self._rd_active
        split, self._rd = self._rd, [0.0] * len(ROUND_PHASES)
        starved, self._sv = self._sv, None
        self._rd_t0, self._rd_index, self._rd_active = now, index + 1, 0
        if self.watchdog is not None:
            self.watchdog.step_finished()
        # the rows of the state arena that hold a seated request's state
        self.metrics.state_rows_in_use = self._n_active * self._state_layers
        dur = now - t0
        if _tracer.enabled:
            _tracer.add_complete(
                "lm/round", t0, dur, cat="serve",
                args={"round": index, "active": active,
                      "admitted": admitted})
        # plain: a decode round that nothing interrupted -- what the
        # running median, and so the slow-round rule, is taken over
        plain = (admitted == 0 and split[P_PREFILL] == 0.0
                 and split[P_WAIT] > 0.0)
        median = self.metrics.record_round(t0, dur, split, index, active,
                                           admitted, plain, starved)
        if median is not None:
            log.warning(
                "lm engine %s: slow round %d: %.3f s, %.1f x the running "
                "median of %.4f s (active %d, admitted %d, starved %.3f s, "
                "%.3f s after start); seconds by phase: %s", self.name,
                index, dur, dur / median, median, active, admitted,
                sum(starved) if starved else 0.0,
                t0 - self.metrics.started_at,
                {p: round(v, 4)
                 for p, v in zip(ROUND_PHASES[1:], split[1:]) if v})

    def _admit_each(self, kind: str, admit, pairs: list) -> list:
        """Run one kind of admission (``admit(slot, item)`` -> seated?)
        over ``(slot, item)`` pairs, each inside its ``lm/admit``
        envelope; a failure fails that stream and frees its slot.
        Returns the pairs deferred under pool pressure."""
        deferred = []
        for slot, item in pairs:
            seated = None
            t0 = self._stamp(P_ADMIT_HOST)
            if _tracer.enabled:
                self._adm_note = {}
                if _tracer.sampled(item.rid):
                    self._adm_rid = item.rid
            try:
                seated = admit(slot, item)
            except BaseException as e:  # noqa: BLE001
                item.stream._finish(error=e)
                with self._cv:
                    self._free.append(slot)
            else:
                if not seated:
                    deferred.append((slot, item))
            now = self._stamp(P_SCHED)
            if _tracer.enabled:
                args = {"kind": kind, "round": self._rd_index, "slot": slot,
                        "prompt_len": int(len(item.stream.prompt)),
                        "deferred": seated is False}
                if seated is None:
                    args["error"] = True
                if self._adm_note:
                    args.update(self._adm_note)
                if self._adm_rid is not None:
                    args["request_id"] = self._adm_rid
                _tracer.add_complete("lm/admit", t0, now - t0, cat="serve",
                                     args=args)
            self._adm_rid = self._adm_note = None
        return deferred

    def _runs_ahead(self) -> bool:
        """Whether the next decode round may be enqueued before the one
        on the device is collected: nothing needs the host in between.
        Nobody waits who could be seated (on a free slot, or on one that
        the round in flight vacates BY ITS COUNT; a stream's eos cannot
        be known, so its slot runs ahead and the row is discarded if it
        had ended), no chunk is left to prefill, nothing is to hibernate,
        no cancel has nudged, the engine is not closing, and some slot
        goes on into the next round.  What this cannot see, a seated
        stream cancelled or past its deadline, ``_lifecycle_dead`` says.
        Caller holds ``_cv``."""
        rnd = self._flying
        if (rnd is None or self._closing or self._abort or self._lc_nudge
                or self._prefilling or self._hibernate_req):
            return False
        n_last = rnd.n_last
        if self._selfdraft:
            # what the round in flight accepted is not known yet: a slot of
            # it with two tokens or fewer left MAY end with it, and a
            # sampled slot's next keys follow how many tokens it emitted
            rows = [st for i, st, _ in rnd.rows if self._slots[i] is st]
            if any(st.temperature > 0.0 for st in rows):
                return False
            n_last = sum(st.remaining <= 2 for st in rows)
        # the seated slots that decode in the next round
        going_on = self._n_active - n_last
        if going_on <= 0:
            return False
        return not ((self._queue or self._adopt_q or self._resume_q)
                    and (self._free or n_last)
                    and going_on < self._slot_limit)

    def _run(self):
        try:
            while True:
                with self._cv:
                    if self._nothing_to_do():
                        # the instants since the last round's end were
                        # idle, not the start of a round
                        self._ph = P_IDLE
                        if self._ph_ann is not None:
                            self._annotate(P_IDLE)
                        while self._nothing_to_do():
                            if not self._cv.wait(self._lc_wait_timeout()):
                                # a holding station's deadline came due
                                # while the engine idled (e.g. a hibernated
                                # stream): run the sweep
                                self._lc_nudge = True
                        self._rd_t0 = self._stamp(P_SCHED)
                    if self._abort:
                        break
                    if (self._closing and not self._queue
                            and not self._adopt_q and not self._resume_q
                            and not self._n_active
                            and not self._prefilling):
                        # break (not return): the bottom _fail_all
                        # resolves any still-hibernated streams with
                        # ServingClosed instead of leaving them hanging
                        break
                    if self.watchdog is not None:
                        self.watchdog.step_started()
                    ahead = self._runs_ahead()
                    # cancelled/expired requests leave their holding
                    # stations BEFORE this round admits anything
                    self._lifecycle_sweep_locked()
                # a seated stream to honour needs the host as well
                dead = self._lifecycle_dead() if ahead else None
                if ahead and not dead:
                    # round n+1 goes to the device, then round n's ids are
                    # read: completion, the copy, emission and this
                    # dispatch hide behind a module
                    nxt = self._dispatch(ahead=True)
                    self._collect(self._flying)
                    self._flying = nxt
                    self._round_end(0)
                    continue
                if self._flying is not None:
                    # a drain: what follows needs the slots as the round
                    # in flight leaves them
                    self._collect(self._flying)
                    self._flying = None
                    self._stamp(P_SCHED)
                admitted = self._seat_waiting()
                self._lifecycle_round(dead)
                if self._hibernate_req:
                    self._service_hibernations()
                if self._chunk_cap is not None and self._prefilling:
                    # Sarathi interleave: ONE bounded chunk of the
                    # oldest in-progress prefill per scheduler round,
                    # then back to decoding — the decode stall per
                    # round is one chunk, not one prompt
                    pf = self._prefilling[0]
                    self._stamp(P_ADMIT_HOST)
                    if _tracer.sampled(pf.req.rid):
                        self._adm_rid = pf.req.rid
                    try:
                        if self._prefill_chunk(pf):
                            self._prefilling.popleft()
                            self._finish_prefill(pf)
                    except BaseException as e:  # noqa: BLE001
                        self._prefilling.popleft()
                        self.pool.release(pf.blocks)
                        pf.req.stream._finish(error=e)
                        with self._cv:
                            self._free.append(pf.slot)
                    self._stamp(P_SCHED)
                    self._adm_rid = self._adm_note = None
                if self._n_active:
                    if self.draft is not None and not self._selfdraft:
                        self._step_spec()
                    else:
                        # left on the device: the next round of the loop
                        # runs ahead of it or collects it first
                        self._flying = self._dispatch(ahead=False)
                self._round_end(admitted)
        except BaseException as e:  # noqa: BLE001
            self._fail_all(e)
            return
        finally:
            # leave no annotation entered, no round in flight and no
            # starved interval open behind a thread that is gone
            self._annotate(None)
            self._trace_starved_tail()
            if self.watchdog is not None:
                self.watchdog.step_finished()
        self._fail_all(ServingClosed("engine closed before completion"))

    def _seat_waiting(self) -> int:
        """Hand the free slots to who waits -- adoptions, resumes, then
        submissions -- and run their admissions; returns how many were
        seated.  No decode round is on the device."""
        with self._cv:
            # in-flight = decoding + mid-prefill: both hold slots
            inflight = self._n_active + len(self._prefilling)
            adopts = []
            # adoptions outrank submissions: their TTFT is paid
            while (self._free and self._adopt_q
                   and (inflight + len(adopts)) < self._slot_limit):
                adopts.append((self._free.pop(),
                               self._adopt_q.popleft()))
            # resumes rank with adoptions (same reason) but
            # after them: a migrated chain in transit is hotter
            # than a hibernated one at rest
            resumes = []
            while (self._free and self._resume_q
                   and (inflight + len(adopts) + len(resumes))
                   < self._slot_limit):
                resumes.append((self._free.pop(),
                                self._resume_q.popleft()))
            admits = []
            while (self._free and self._queue
                   and (inflight + len(adopts) + len(resumes)
                        + len(admits)) < self._slot_limit):
                admits.append((self._free.pop(),
                               self._queue.popleft()))
        if self.migrate is not None:
            # prefill-phase occupancy: one sample per scheduler
            # round (a prefill replica has no decode steps, so
            # this is the phase's slot-utilization signal; its
            # decode_steps gauge reads as scheduler rounds)
            self.metrics.record_step(
                min(self.slots,
                    inflight + len(adopts) + len(admits)), [])
        deferred_adopts = self._admit_each(
            "adopt", self._adopt_into, adopts)
        deferred_resumes = self._admit_each(
            "resume", self._resume_into, resumes)
        deferred = self._admit_each("submit", self._admit, admits)
        if deferred or deferred_adopts or deferred_resumes:
            # pool pressure: requeue at the FRONT (FIFO order
            # preserved) and return the slots — blocks free as
            # active streams finish, then admission retries
            with self._cv:
                for slot, req in reversed(deferred):
                    self._free.append(slot)
                    self._queue.appendleft(req)
                for slot, h in reversed(deferred_adopts):
                    self._free.append(slot)
                    self._adopt_q.appendleft(h)
                for slot, hib in reversed(deferred_resumes):
                    self._free.append(slot)
                    self._resume_q.appendleft(hib)
                if not self._n_active and not self._prefilling:
                    # nothing in flight to free capacity (a
                    # ledger-watermark deferral with idle
                    # slots): wait briefly instead of spinning
                    # on the retry
                    self._cv.wait(0.05)
        return (len(adopts) + len(resumes) + len(admits)
                - len(deferred_adopts) - len(deferred_resumes)
                - len(deferred))

    # -- request lifecycle (deadlines / cooperative cancel) ------------- #
    def _lc_wake(self):
        """Client-side nudge (installed as ``LMStream._wake_cb``): a
        cancel must wake an idle worker so it is honored at the NEXT
        scheduler round, not the next organic one."""
        with self._cv:
            self._lc_nudge = True
            self._cv.notify_all()

    def _lc_count(self, key: str, n: int = 1) -> None:
        if n <= 0:
            return
        with self._lc_lock:
            self.lifecycle[key] += n
        self._lc_counters[key].add(n)

    def _lc_wait_timeout(self) -> Optional[float]:
        """Earliest pending deadline across slot-less holding stations
        (queued / adoption / resume / hibernated), as a cv-wait bound —
        an idle engine must still wake to shed an expiring hibernated
        stream.  Caller holds ``_cv``; None = no deadline pending."""
        dls = [r.stream.deadline_at for r in self._queue]
        dls += [h.stream.deadline_at for h in self._adopt_q]
        dls += [h.stream.deadline_at for h in self._resume_q]
        dls += [h.stream.deadline_at for h in self._hibernated.values()]
        dls = [d for d in dls if d is not None]
        if not dls:
            return None
        return max(0.0, min(dls) - time.monotonic()) + 0.005

    def _lc_shed_queued(self, stream: LMStream, rid) -> None:
        """A queued (never-prefilled) request left the lifecycle: a
        cancel truncates quietly; a blown deadline is the typed
        pre-admission shed — counted exactly like an admission-control
        rejection (``ServingDeadlineExceeded`` is a
        ``ServingOverloaded``), so SLO/goodput accounting holds."""
        if stream.cancel_requested:
            reason = "cancelled"
            self._lc_count("cancelled")
            stream._finish_truncated("cancelled")
        else:
            reason = "deadline"
            self.metrics.record_reject()
            count_rejection()
            self._lc_count("expired_preadmission")
            stream._finish(error=ServingDeadlineExceeded(
                f"deadline ({stream.deadline_s}s) expired before "
                "prefill; request shed pre-admission"))
        if _tracer.sampled(rid):
            _tracer.instant("lm/lifecycle_shed", cat="serve",
                            request_id=rid, reason=reason,
                            station="queue")

    def _lc_truncate(self, stream: LMStream, rid, *,
                     station: str = "seated") -> None:
        """Finish a request that progressed past admission (blocks
        were allocated / tokens may have been emitted) with the typed
        truncation marker; tokens already emitted stay valid."""
        if stream.cancel_requested:
            reason = "cancelled"
            self._lc_count("cancelled")
        else:
            reason = "deadline"
            self._lc_count("expired_midstream")
        stream._finish_truncated(reason)
        self.metrics.record_complete()
        if _tracer.sampled(rid):
            _tracer.instant("lm/lifecycle_truncate", cat="serve",
                            request_id=rid, reason=reason,
                            station=station,
                            at_tokens=len(stream.generated))

    def _lifecycle_sweep_locked(self) -> None:
        """Shed cancelled/expired requests from every holding station
        that owns NO decode slot: the admission queue (the pre-prefill
        shed), the adoption queue (pre-seat; its retained decode-pool
        blocks release), the resume queue, and the hibernated set —
        hibernated streams are cancellable WITHOUT resume: the chain
        drops straight out of the host tier, no promote transfer.
        Caller holds ``_cv``."""
        self._lc_nudge = False
        now = time.monotonic()

        def _dead(stream):
            return stream.cancel_requested or stream.expired(now)

        if any(_dead(r.stream) for r in self._queue):
            live = []
            while self._queue:
                r = self._queue.popleft()
                if _dead(r.stream):
                    self._lc_shed_queued(r.stream, r.rid)
                else:
                    live.append(r)
            self._queue.extend(live)
        if any(_dead(h.stream) for h in self._adopt_q):
            live = []
            while self._adopt_q:
                h = self._adopt_q.popleft()
                if _dead(h.stream):
                    if h.matched:
                        self.pool.release(h.matched)
                    self._lc_truncate(h.stream, h.rid, station="adopt_q")
                else:
                    live.append(h)
            self._adopt_q.extend(live)
        if any(_dead(h.stream) for h in self._resume_q):
            live = []
            while self._resume_q:
                hib = self._resume_q.popleft()
                if _dead(hib.stream):
                    # a popped payload rides the handle; dropping the
                    # handle drops the chain
                    self._lc_truncate(hib.stream, hib.rid,
                                      station="resume_q")
                else:
                    live.append(hib)
            self._resume_q.extend(live)
        for rid in [rid for rid, hib in self._hibernated.items()
                    if _dead(hib.stream)]:
            hib = self._hibernated.pop(rid)
            try:
                if self.kvtier is not None:
                    self.kvtier.get(("session", rid), pop=True)
            except Exception:
                pass
            self._lc_truncate(hib.stream, rid, station="hibernated")

    def _lifecycle_dead(self) -> bool:
        """Whether a stream that holds a decode slot is cancelled or past
        its deadline.  The ``serving.cancel`` fault site crosses here —
        one crossing per seated stream per round, and an injected
        fault IS that client disconnecting (how the chaos replayer
        makes a disconnect storm)."""
        from bigdl_tpu.resilience.faults import fault_point
        seated = [st.stream for st in self._slots if st is not None]
        seated += [pf.req.stream for pf in self._prefilling]
        for s in seated:
            try:
                fault_point("serving.cancel", name=self.name,
                            rid=s.request_id)
            except (TransientBackendError, BackendLostError):
                s.cancel()
        now = time.monotonic()
        return any(s.cancel_requested or s.expired(now) for s in seated)

    def _lifecycle_round(self, dead: Optional[bool] = None) -> None:
        """Per-round lifecycle pass over the stations that DO hold a
        decode slot (``dead``: what ``_lifecycle_dead`` said, where this
        round of the loop has asked already): cancelled/expired streams
        are honored same-iteration: slot recycled, blocks released,
        drafter state dropped, stream finished with the typed
        truncation marker.  No decode round is on the device."""
        if dead is None:
            dead = self._lifecycle_dead()
        if not dead:
            return
        now = time.monotonic()

        def _dead(stream):
            return stream.cancel_requested or stream.expired(now)

        with self._cv:
            if any(_dead(pf.req.stream) for pf in self._prefilling):
                live = []
                while self._prefilling:
                    pf = self._prefilling.popleft()
                    if _dead(pf.req.stream):
                        self.pool.release(pf.blocks)
                        self._free.append(pf.slot)
                        self._lc_truncate(pf.req.stream, pf.req.rid,
                                          station="prefilling")
                    else:
                        live.append(pf)
                self._prefilling.extend(live)
            freed = False
            for i, st in enumerate(self._slots):
                if st is None or not _dead(st.stream):
                    continue
                s = st.stream
                # decode steps spent between the cancel landing and
                # this round honoring it were wasted: count them
                if s.cancel_requested:
                    self._lc_count(
                        "wasted_decode_steps",
                        max(0, len(s._tokens) - s._cancel_at_gen))
                # identical cleanup to the EOS free path: refcounts
                # are conserved and the slot is reusable THIS round
                self._trace_done(s, st.rid)
                self.pool.release(st.blocks)
                self._slots[i] = None
                if self.draft is not None:
                    self.draft.release(i)
                self._free.append(i)
                self._n_active -= 1
                self._lc_truncate(s, st.rid)
                freed = True
            if freed:
                self._cv.notify_all()

    def _mem_pressure_deferred(self) -> bool:
        """Byte-level admission gate: when the memory ledger reads the
        device past its used-fraction watermark, defer the admission
        exactly like pool pressure — and let the ledger dump ONE
        ``mem_pressure`` flight bundle while the attribution table can
        still be written (a RESOURCE_EXHAUSTED later could not)."""
        try:
            from bigdl_tpu.obs.ledger import get_ledger
            led = get_ledger()
            if led.over_watermark():
                led.check_pressure(
                    context={"site": f"lm_admission/{self.name}"})
                return True
        except Exception:
            pass
        return False

    def _admit(self, slot: int, req: _Request) -> bool:
        """Prefill + insert one request into ``slot``.  Returns False
        (defer) when the pool can't supply its blocks right now — even
        after evicting unreferenced radix tails — or when the memory
        ledger reports device bytes past the watermark."""
        if self._mem_pressure_deferred():
            return False
        t = req.prompt0.shape[0]
        B = self.block_len
        need_total = self.pool.blocks_for(t + req.max_new)
        matched: List[int] = []
        if self.radix is not None:
            matched = self.radix.match(req.prompt0)  # retains for us
            if self.kvtier is not None:
                # a prefix that fell out of HBM may have survived a
                # tier down: promote its continuation back and extend
                # the match (prefill only past it)
                matched = self._promote_extend(req.prompt0, matched,
                                               rid=req.rid)
        traced = _tracer.sampled(req.rid)
        if self._adm_note is not None:
            self._adm_note["matched_tokens"] = len(matched) * B
        if traced and self.radix is not None:
            _tracer.instant("lm/radix_match", cat="serve",
                            request_id=req.rid,
                            matched_blocks=len(matched),
                            matched_tokens=len(matched) * B,
                            prompt_len=t)
        n_new = need_total - len(matched)
        try:
            self._window_room(t - len(matched) * B, need_total)
            fresh = self.pool.alloc(n_new)
        except PoolExhausted:
            if self.radix is not None:
                self.radix.evict(n_new - self.pool.free_count)
            try:
                self._window_room(t - len(matched) * B, need_total)
                fresh = self.pool.alloc(n_new)
            except PoolExhausted:
                if matched:
                    self.pool.release(matched)
                return False
        blocks = matched + fresh
        self.metrics.record_admission(t, len(matched) * B)
        if traced:
            # queue wait is known only now, at successful admission —
            # retroactive, the batcher's serve/queue_wait idiom
            wait = time.perf_counter() - req.stream.submitted_at
            _tracer.add_complete("lm/queue_wait",
                                 req.stream.submitted_at, wait,
                                 cat="request",
                                 args={"request_id": req.rid, "slot": slot})
        if self._chunk_cap is not None:
            # chunk-interleaved mode: allocation happens at admission
            # (all-or-nothing, same defer semantics), but the prefill
            # itself advances one bounded chunk per scheduler round in
            # _run — decode rounds run in between
            self._prefilling.append(_Prefill(req, blocks, slot,
                                             len(matched) * B))
            return True
        try:
            self._prefill_into(req, blocks, slot, len(matched) * B)
        except BaseException:
            self.pool.release(blocks)
            raise
        return True

    def _window_claim(self, prefill_tokens: int, total_blocks: int) -> dict:
        """What a request may hold of each windowed class at once: the
        window's blocks and the one being written, plus the blocks of the
        largest piece its prefill runs (a chunk's, or the prompt's where it
        is shorter) -- never more than its whole chain's ``total_blocks``."""
        piece = min(self._chunk_blocks,
                    self.pool.blocks_for(max(int(prefill_tokens), 0)) + 1)
        return {i: min(int(total_blocks), need + piece)
                for i, need in self._window_need.items()}

    def _window_owed(self) -> dict:
        """Blocks of each windowed class that the sequences in flight may
        still come for: a decoding one the window's less what it holds, a
        prefilling one its claim's (:meth:`_window_claim`)."""
        owed = {i: 0 for i in self._window_need}
        for st in self._slots:
            if st is not None:
                for i, need in self._window_need.items():
                    owed[i] += max(0, need - self.pool.held(
                        st.blocks[st.marks[i]:st.pos_next // self.block_len
                                  + 1], i))
        for pf in self._prefilling:
            claim = self._window_claim(pf.t - pf.p, len(pf.blocks))
            for i in owed:
                owed[i] += max(0, claim[i] - self.pool.held(
                    pf.blocks[pf.marks[i]:pf.p // self.block_len + 1], i))
        return owed

    def _window_room(self, prefill_tokens: int, total_blocks: int) -> None:
        """Admission by the class that is short: a new request's claim on a
        windowed class has to fit beside what the sequences in flight may
        still come for (evicting what only the prefix cache holds first),
        else :class:`PoolExhausted`, which defers it."""
        if not self._window_need:
            return
        claim, owed = (self._window_claim(prefill_tokens, total_blocks),
                       self._window_owed())
        for i in claim:
            short = claim[i] + owed[i] - self.pool.free_in(i)
            if short > 0 and self.radix is not None:
                self.radix.evict(short)
                short = claim[i] + owed[i] - self.pool.free_in(i)
            if short > 0:
                raise PoolExhausted(
                    f"class {i} (window {self.pool.classes[i].window}) is "
                    f"{short} blocks short of the request's {claim[i]}")

    def _advance(self, holder, pos: int, upto: int) -> None:
        """Move ``holder``'s (a slot's, a prefill's) windowed classes on
        (``BlockPool.advance``): let go of what lies behind the window of a
        query at ``pos``, allot what positions below ``upto`` are written
        to; a class that is short evicts what only the prefix cache holds
        and tries once more."""
        if not self.pool.windowed:
            return
        marks = holder.marks
        # nothing to do until the write position leaves the blocks allotted
        # or a block falls wholly behind a window (every block_len rounds of
        # a decoding slot): the sequence's own two notes, beside its marks
        if upto <= marks["upto"] and pos < marks["pos"]:
            return
        lo = min(marks[i] for i in self.pool.windowed)
        try:
            out = self.pool.advance(holder.blocks, marks, pos, upto)
        except PoolExhausted:
            if self.radix is None:
                raise
            self.radix.evict(self._chunk_blocks)
            out = self.pool.advance(holder.blocks, marks, pos, upto)
        B = self.block_len
        marks["upto"] = min(self.pool.blocks_for(upto), len(holder.blocks)) * B
        marks["pos"] = min((marks[i] + 1) * B + self._windows[i] - 1
                           for i in self.pool.windowed)
        if any(out):
            self.metrics.record_window(*out)
            table = getattr(holder, "table", None)
            if table is not None:       # a slot's: the entries that moved
                hi = min(self.pool.blocks_for(upto), len(holder.blocks))
                table[:, lo:hi] = self.pool.table(holder.blocks[lo:hi],
                                                  hi - lo)

    def _adopt_into(self, slot: int, h: KVHandoff) -> bool:
        """Seat a migrated request into ``slot``: adopt its wire
        payload into this pool (or re-prefill locally when the payload
        was lost in transit) and enter decode at the exact position the
        prefill replica left off.  Returns False (defer) under pool
        pressure — the handoff's pre-retained ``matched`` blocks stay
        held across the deferral, same as a matched radix head."""
        t = h.prompt0.shape[0]
        B = self.block_len
        need_total = self.pool.blocks_for(t + h.max_new)
        if need_total > self.pool.capacity:
            raise RequestExceedsPool(
                f"migrated request needs {need_total} blocks; decode "
                f"pool capacity is {self.pool.capacity}")
        req = _Request(h.stream, h.prompt0, h.max_new, h.temperature,
                       h.eos0, None, h.step_keys, h.rid)
        matched = list(h.matched)
        if h.payload is None:
            # wire payload lost (backend_lost at the migrate fault
            # site): recompute the KV here.  Deterministic prefill ⇒
            # bit-identical rows; the first token is NOT re-picked or
            # re-emitted (handoff carries it), so the stream is exact.
            self.re_prefills += 1
            n_new = need_total - len(matched)
            try:
                fresh = self.pool.alloc(n_new)
            except PoolExhausted:
                if self.radix is not None:
                    self.radix.evict(n_new - self.pool.free_count)
                try:
                    fresh = self.pool.alloc(n_new)
                except PoolExhausted:
                    return False
            blocks = matched + fresh
            pf = _Prefill(req, blocks, slot, len(matched) * B, handoff=h)
            if self._chunk_cap is not None:
                self._prefilling.append(pf)
                return True
            try:
                while not self._prefill_chunk(pf):
                    pass
                self._finish_prefill(pf)
            except BaseException:
                self.pool.release(blocks)
                raise
            return True
        n_wire = int(h.payload["blocks"])
        extra = need_total - len(matched) - n_wire
        if extra < 0:
            raise ValueError(
                f"wire carries {n_wire} blocks but only "
                f"{need_total - len(matched)} are unmatched")
        try:
            fresh = self.pool.adopt_chain(
                h.payload["k"], h.payload["v"], extra_blocks=extra,
                device=self.pool.k.sharding)
        except PoolExhausted:
            if self.radix is not None:
                self.radix.evict(n_wire + extra - self.pool.free_count)
            try:
                fresh = self.pool.adopt_chain(
                    h.payload["k"], h.payload["v"], extra_blocks=extra,
                    device=self.pool.k.sharding)
            except PoolExhausted:
                return False
        blocks = matched + fresh
        self.adopted += 1
        self._prefill_since_step = True  # adoption interrupts decode
        if self.radix is not None:
            # cache the adopted prompt for future prefix hits on THIS
            # pool — sharing survives the hop in both directions
            nfull = t // B
            if nfull:
                self.radix.insert(h.prompt0[:nfull * B], blocks[:nfull])
        if _tracer.sampled(h.rid):
            _tracer.instant("lm/adopt", cat="serve", request_id=h.rid,
                            slot=slot, wire_blocks=n_wire,
                            matched_blocks=len(matched), src=h.src_name)
        self._seat(req, t, h.first0, blocks, slot)
        return True

    # -- tiered KV memory (host tier + hibernation) --------------------- #
    def _demote_block(self, path, block: int) -> None:
        """Radix ``on_evict`` hook: gather the victim block's k/v rows
        (plus scales, when quantized — atomically, same payload) and
        demote them into the host tier keyed by the block's
        token-prefix path.  Runs while the block is still allocated."""
        wire = self.pool.export_chain([block])
        entry = {kk: wire[kk] for kk in ("k", "v", "ks", "vs")
                 if kk in wire}
        self.kvtier.put(("radix",) + tuple(path), entry)
        _tracer.instant("kvtier/demote", cat="serve", block=int(block),
                        depth=len(path))

    def _promote_extend(self, prompt0, matched: List[int], *,
                        rid=None) -> List[int]:
        """Extend a radix-matched head with consecutive host-tier
        blocks: each surviving continuation block is adopted back into
        HBM (over the 32 MB chunked transfer), registered in the trie,
        and appended to the match — the admission then prefills only
        past the combined prefix.  Best-effort: pool pressure or a
        tier miss just returns the match as-is."""
        t = prompt0.shape[0]
        B = self.block_len
        cap = max(0, (t - 1) // B)
        m = len(matched)
        if m >= cap or self.radix is None:
            return matched
        from bigdl_tpu.serving.kvtier.store import block_path
        keys = block_path(prompt0, B, cap)
        payloads = []
        for i in range(m, cap):
            p = self.kvtier.get(("radix",) + keys[:i + 1])
            if p is None:
                break
            payloads.append(p)
        if not payloads:
            return matched
        quant = self.kv_quant is not None
        if (payloads[0]["k"].shape[1:] != self.pool.wire_shape
                or (quant and "ks" not in payloads[0])):
            # stale entries from a different geometry/precision under
            # the same store name: not promotable into this pool
            return matched
        k = np.concatenate([p["k"] for p in payloads], axis=0)
        v = np.concatenate([p["v"] for p in payloads], axis=0)
        ks = (np.concatenate([p["ks"] for p in payloads], axis=0)
              if quant else None)
        vs = (np.concatenate([p["vs"] for p in payloads], axis=0)
              if quant else None)
        nbytes = k.nbytes + v.nbytes
        if quant:
            nbytes += ks.nbytes + vs.nbytes
        rid_args = {"request_id": rid} if _tracer.sampled(rid) else {}
        t0 = time.perf_counter()
        with _tracer.span("kvtier/promote", cat="serve",
                          blocks=len(payloads), bytes=int(nbytes),
                          **rid_args):
            try:
                fresh = self.pool.adopt_chain(
                    k, v, ks, vs, extra_blocks=0,
                    device=self.pool.k.sharding)
            except PoolExhausted:
                # promotion is best-effort — never deepen the very
                # pressure it is trying to relieve
                return matched
        self.kvtier.record_promote(nbytes, time.perf_counter() - t0)
        n_total = m + len(fresh)
        out = list(matched) + fresh
        # trie registration: future admissions share the promoted
        # blocks straight from HBM, and the trie's reference keeps
        # them demotable again once every stream lets go
        self.radix.insert(prompt0[:n_total * B], out)
        with self.radix._lock:
            # promoted blocks save suffix prefill exactly like a trie
            # hit — fold them into the same saved-tokens ledger
            self.radix.matched_tokens += len(fresh) * B
        return out

    def attach_radix_summary(self, summary) -> None:
        """Publish this engine's radix trie to the serving router: the
        summary mirrors the trie's prefix fingerprints (refreshed by
        the per-node insert/evict hooks, O(1) each), so a router can
        score this replica's cache affinity without ever touching the
        trie.  See :mod:`bigdl_tpu.serving.router.summary`."""
        if self.radix is None:
            raise ValueError(
                "attach_radix_summary requires enable_prefix_cache=True")
        self.radix.attach_summary(summary)
        self.radix_summary = summary

    def hibernate(self, stream: LMStream, *,
                  timeout: Optional[float] = 30.0) -> bool:
        """Swap an idle stream out of its decode slot: its written KV
        chain moves to the host tier (``("session", rid)``), its slot
        and every HBM block free, and its full sampling state is kept
        so :meth:`resume` continues the stream bit-exactly on the next
        token.  Blocks until the worker performs the swap (it owns the
        slots).  Returns True once hibernated; False when the stream
        is not currently seated in a decode slot (queued, mid-prefill,
        mid-replay, or already finished)."""
        if self.kvtier is None:
            raise ValueError(
                "hibernate requires a kvtier (HostBlockStore)")
        rid = stream.request_id
        with self._cv:
            if rid in self._hibernated:
                return True
            seated = any(st is not None and st.rid == rid
                         and not st.replay for st in self._slots)
            if not seated or stream.done():
                return False
            self._hibernate_req.add(rid)
            self._cv.notify_all()
            self._cv.wait_for(lambda: rid not in self._hibernate_req,
                              timeout)
            self._hibernate_req.discard(rid)
            return rid in self._hibernated

    def resume(self, stream: LMStream) -> bool:
        """Re-admit a hibernated stream: its chain promotes back into
        HBM through the chunked transfer (or, if the tier dropped the
        payload, the prompt re-prefills and the generated tokens
        replay through the decode path — bit-exact either way) and
        decode continues at the exact token it left off.  Resumes
        rank with adoptions, ahead of fresh admissions.  Returns False
        when the stream is not hibernated."""
        rid = stream.request_id
        with self._cv:
            if self._closing:
                raise ServingClosed("LMServingEngine is closed")
            hib = self._hibernated.pop(rid, None)
            if hib is None:
                return False
            self._resume_q.append(hib)
            self._cv.notify_all()
        if _tracer.sampled(rid):
            _tracer.instant("lm/resume_enqueue", cat="serve",
                            request_id=rid,
                            hibernated_s=round(
                                time.perf_counter() - hib.hibernated_at,
                                4))
        return True

    def _service_hibernations(self) -> None:
        """Worker-side swap-out: export each requested seated slot's
        written blocks to the host tier, release the chain, free the
        slot.  Requests for streams no longer seated are discarded so
        their waiters unblock."""
        with self._cv:
            todo = [(i, st) for i, st in enumerate(self._slots)
                    if st is not None and st.rid in self._hibernate_req
                    and not st.replay]
            stale = self._hibernate_req - {st.rid for _, st in todo}
            if stale:
                self._hibernate_req -= stale
                self._cv.notify_all()
        for i, st in todo:
            self._hibernate_one(i, st)

    def _hibernate_one(self, slot: int, st: _Slot) -> None:
        n_used = self.pool.blocks_for(st.pos_next)
        rid_args = ({"request_id": st.rid}
                    if _tracer.sampled(st.rid) else {})
        with _tracer.span("kvtier/hibernate", cat="serve", slot=slot,
                          blocks=n_used, **rid_args):
            wire = self.pool.export_chain(st.blocks[:n_used])
            entry = {kk: wire[kk] for kk in ("k", "v", "ks", "vs")
                     if kk in wire}
            self.kvtier.put(("session", st.rid), entry)
        hib = _Hibernated(st, n_used)
        self.pool.release(st.blocks)
        if self.draft is not None:
            # the drafter's dense per-slot cache does not hibernate;
            # the resumed stream rides plain decode (still bit-exact)
            self.draft.release(slot)
        with self._cv:
            self._slots[slot] = None
            self._free.append(slot)
            self._n_active -= 1
            self._hibernate_req.discard(st.rid)
            self._hibernated[st.rid] = hib
            self.hibernations += 1
            self._cv.notify_all()

    def _resume_into(self, slot: int, hib: _Hibernated) -> bool:
        """Seat a hibernated stream back into ``slot``.  Returns False
        (defer) under pool pressure — a popped payload stays cached on
        the handle across deferrals, never re-read or lost."""
        stream = hib.stream
        t = int(stream.prompt.shape[0])
        prompt0 = (stream.prompt.astype(np.int32) - 1)
        max_new = int(stream.max_new)
        need_total = self.pool.blocks_for(t + max_new)
        B = self.block_len
        rid_args = ({"request_id": hib.rid}
                    if _tracer.sampled(hib.rid) else {})
        req = _Request(stream, prompt0, max_new, hib.temperature,
                       hib.eos0, None, hib.step_keys, hib.rid)
        if not hib.fetched:
            hib.payload = self.kvtier.get(("session", hib.rid), pop=True)
            hib.fetched = True
        if hib.payload is not None:
            payload = hib.payload
            n_wire = int(payload["k"].shape[0])
            extra = need_total - n_wire
            nbytes = sum(int(payload[x].nbytes) for x in payload)
            t0 = time.perf_counter()
            with _tracer.span("kvtier/promote", cat="serve",
                              blocks=n_wire, bytes=int(nbytes),
                              session=1, **rid_args):
                try:
                    fresh = self.pool.adopt_chain(
                        payload["k"], payload["v"],
                        payload.get("ks"), payload.get("vs"),
                        extra_blocks=extra,
                        device=self.pool.k.sharding)
                except PoolExhausted:
                    if self.radix is not None:
                        self.radix.evict(n_wire + extra
                                         - self.pool.free_count)
                    try:
                        fresh = self.pool.adopt_chain(
                            payload["k"], payload["v"],
                            payload.get("ks"), payload.get("vs"),
                            extra_blocks=extra,
                            device=self.pool.k.sharding)
                    except PoolExhausted:
                        return False
            self.kvtier.record_promote(nbytes, time.perf_counter() - t0)
            blocks = fresh
            if self.radix is not None:
                nfull = t // B
                if nfull:
                    self.radix.insert(prompt0[:nfull * B],
                                      blocks[:nfull])
            self._seat_resumed(req, hib, blocks, slot,
                               pos_next=hib.pos_next, last0=hib.last0,
                               remaining=hib.remaining,
                               step_idx=hib.step_idx, replay=())
            _tracer.instant("lm/resume", cat="serve", slot=slot,
                            wire_blocks=n_wire, **rid_args)
            return True
        # payload lost (capacity-dropped or corrupt spill): rebuild.
        # Prompt KV recomputes through the same deterministic prefill
        # admission ran; the generated tokens' KV rebuilds by REPLAYING
        # them through the decode path that wrote the originals — both
        # legs bit-identical, no token is ever re-emitted.
        emitted0 = np.asarray(stream.generated, np.int32) - 1
        matched: List[int] = []
        if self.radix is not None:
            matched = self.radix.match(prompt0)
            matched = self._promote_extend(prompt0, matched,
                                           rid=hib.rid)
        n_new = need_total - len(matched)
        try:
            fresh = self.pool.alloc(n_new)
        except PoolExhausted:
            if self.radix is not None:
                self.radix.evict(n_new - self.pool.free_count)
            try:
                fresh = self.pool.alloc(n_new)
            except PoolExhausted:
                if matched:
                    self.pool.release(matched)
                return False
        blocks = matched + fresh
        self.resume_re_prefills += 1
        pf = _Prefill(req, blocks, slot, len(matched) * B)
        try:
            while not self._prefill_chunk(pf):
                pass
        except BaseException:
            self.pool.release(blocks)
            raise
        if self.radix is not None:
            nfull = t // B
            if nfull:
                self.radix.insert(prompt0[:nfull * B], blocks[:nfull])
        self._seat_resumed(req, hib, blocks, slot, pos_next=t,
                           last0=int(emitted0[0]),
                           remaining=max_new - 1, step_idx=0,
                           replay=tuple(int(x) for x in emitted0[1:]))
        _tracer.instant("lm/resume", cat="serve", slot=slot,
                        re_prefill=1, replay=len(emitted0) - 1,
                        **rid_args)
        return True

    def _seat_resumed(self, req: _Request, hib: _Hibernated,
                      blocks: List[int], slot: int, *, pos_next: int,
                      last0: int, remaining: int, step_idx: int,
                      replay) -> None:
        st = _Slot(req, pos_next, last0, blocks,
                   self.pool.table(blocks, self.table_width))
        st.remaining = int(remaining)
        st.step_idx = int(step_idx)
        st.replay = deque(replay)
        # resumed streams ride plain decode (draft_ok stays False) and
        # interrupt the ITL stream the way an adoption does
        self._prefill_since_step = True
        with self._cv:
            self._slots[slot] = st
            self._n_active += 1
            self.resumes += 1

    @staticmethod
    def _trace_done(stream: LMStream, rid: Optional[str]) -> None:
        """Retroactive per-request ROOT span (submit -> finish) — the
        natural parent every lm/* event of the request nests under in
        ``Tracer.span_tree``.  Recorded at completion because only then
        is the request's full extent known."""
        if not _tracer.sampled(rid):
            return
        end = stream.finished_at
        if end is None:
            end = time.perf_counter()
        _tracer.add_complete(
            "lm/request", stream.submitted_at,
            end - stream.submitted_at, cat="request",
            args={"request_id": rid, "prompt_len": int(len(stream.prompt)),
                  "max_new": stream.max_new,
                  "emitted": len(stream._tokens)})

    def _prefill_into(self, req: _Request, blocks: List[int], slot: int,
                      matched_len: int,
                      handoff: Optional[KVHandoff] = None) -> None:
        """Run-to-completion prefill (the non-interleaved path): every
        chunk back-to-back, then finish."""
        pf = _Prefill(req, blocks, slot, matched_len, handoff)
        while not self._prefill_chunk(pf):
            pass
        self._finish_prefill(pf)

    def _prefill_chunk(self, pf: _Prefill) -> bool:
        """One bucketed prefill pass + block scatter; True when the
        whole prompt is in the arena.  Chunk sizes stay block-aligned
        (except the final remainder) so the suffix path's prefix_len is
        always a whole number of blocks; ``max_prefill_chunk_tokens``
        only lowers the per-chunk ceiling."""
        req, blocks, t = pf.req, pf.blocks, pf.t
        B = self.block_len
        largest = self.prefill_buckets[-1]
        cap = self._chunk_cap
        largest_eff = largest if cap is None else min(largest, cap)
        chunk_full = (self._chunk_full if cap is None
                      else min(self._chunk_full, cap))
        p = pf.p
        rem = t - p
        ts = rem if rem <= largest_eff else chunk_full
        bucket = self.bucket_for(ts)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :ts] = req.prompt0[p:p + ts]
        # the chunk's k/v scatter into its (block-aligned) blocks;
        # bucket-padding rows land in trailing owned blocks or the
        # scratch block, always masked until overwritten
        nb_w = -(-bucket // B)
        # the chunk's blocks of a windowed class are allotted now, and what
        # lies behind the window of its first query goes
        self._advance(pf, p, p + ts)
        ids_w = self._by_class(self.pool.table(blocks[p // B:p // B + nb_w],
                                               nb_w))
        if self._adm_note is not None:
            self._adm_note["bucket"] = bucket
        # lm/prefill and lm/insert time the ENQUEUE of the chunk program
        # and of the scatter; the device wait lands in lm/first_token
        self._stamp(P_PREFILL)
        if _tracer.enabled:
            self._ph_args = {"bucket": bucket, "prompt_len": t,
                             "prefix_len": p}
            if self.state is not None:
                self._ph_args.update(carried_state=p > 0,
                                     state_layers=self._state_layers)
        if p == 0:
            logits, *rest = self.prefill_cache(
                self._params, self._buffers,
                {"ids": ids, "len": np.int32(ts)})
        else:
            nbp = p // B
            pb = self._prefix_bucket_for(nbp)
            pblocks = self._by_class(self.pool.table(blocks[:nbp], pb))
            x = {"ids": ids, "len": np.int32(ts),
                 "prefix_len": np.int32(p), "blocks": pblocks,
                 "kv": self.pool.arenas, **self._carried_operands(pf.slot)}
            if self._selfdraft and pf.h_last is None:
                # a radix hit: the prefix's rows are cached, the hidden
                # state the first pair needs -- at the last matched position
                # -- is not; one token's pass over the prefix before it
                # computes it (its own rows are not kept)
                one = np.zeros((1, self.prefill_buckets[0]), np.int32)
                one[0, 0] = req.prompt0[p - 1]
                if self._ph_args is not None:
                    self._ph_args["prepass"] = 1
                pf.h_last = self.prefix_prefill_cache(
                    self._params, self._buffers,
                    dict(x, ids=one, len=np.int32(1),
                         prefix_len=np.int32(p - 1),
                         **self._h_prev_operand(None)))[-1]
            x.update(self._h_prev_operand(pf.h_last))
            logits, *rest = self.prefix_prefill_cache(
                self._params, self._buffers, x)
        if self._selfdraft:
            *rest, pf.h_last = rest
        # what the chunk caches: (k, v), or a latent pool's rows
        n = self.pool.data_arenas
        multi = len(self.pool.classes) > 1
        n *= len(self.pool.classes)     # (k, v) a class, side by side
        new, rest = rest[:n], rest[n:]
        if self._moe_layers:    # summed over a prompt's chunks
            moe, *rest = rest
            pf.moe = moe if pf.moe is None else pf.moe + moe
        self._stamp(P_INSERT)
        if _tracer.enabled:
            self._ph_args = {"slot": pf.slot, "bucket": bucket}
        kv = self.pool.arenas
        if self._selfdraft:
            *self.pool.arenas, self._hid = self._insert_compiled(bucket)(
                kv[0], self._hid, *new, ids_w, pf.h_last, np.int32(pf.slot))
        elif multi:
            self.pool.arenas = self._insert_compiled(bucket)(*kv, *new, ids_w)
        else:
            self.pool.arenas = self._insert_compiled(bucket)(
                *kv[:n], *new, ids_w, *kv[n:])
        if self.state is not None:
            # the slot's rows at this chunk's true end: what the next chunk
            # starts from, and after the last what the slot decodes from
            self._stamp(P_STATE_INSERT)
            if _tracer.enabled:
                self._ph_args = {"slot": pf.slot}
            self.state.arenas = self._state_insert_compiled()(
                *self.state.arenas, *rest, np.int32(pf.slot))
        self._stamp(P_ADMIT_HOST)
        self._prefill_since_step = True
        pf.logits = logits
        pf.p = p + ts
        if pf.p < t:    # (the last chunk's go once the trie has the prompt)
            self._advance(pf, pf.p, pf.p)
        return pf.p >= t

    def _finish_prefill(self, pf: _Prefill) -> None:
        req, blocks, slot, t = pf.req, pf.blocks, pf.slot, pf.t
        B = self.block_len
        # cache the prompt's full blocks for future prefix hits (the
        # matched head is already in the trie; only novel tails add)
        if self.radix is not None:
            nfull = t // B
            if nfull:
                self.radix.insert(req.prompt0[:nfull * B], blocks[:nfull])
        # (the trie has its own references now) what lies behind the windows
        # of the first decode round's query goes
        self._advance(pf, t, t)
        if pf.handoff is not None:
            # re-prefill of a migrated request whose wire payload was
            # lost: the first token was already emitted on the prefill
            # replica — recompute the KV rows, discard the logits, and
            # seat decode exactly where the handoff says it stands
            self._seat(req, t, pf.handoff.first0, blocks, slot)
            self._slots[slot].marks = pf.marks
            return
        # where the device wait for the prefill and the insert lands
        self._stamp(P_FIRST_TOKEN)
        logits = np.asarray(pf.logits)  # sync; (1, V) f32
        # (the insert was enqueued behind the prefill: the device is empty
        # once the arenas it returned are ready, which the stamps poll)
        self._dev = _DEV_POLL
        self.metrics.record_logit_rows(logits.shape[0])
        if pf.moe is not None:
            landed = int(np.asarray(pf.moe)[0])
            self.metrics.record_moe_prefill(landed)
            if _tracer.enabled:
                self._ph_args = {"moe_assignments": landed}
        first0 = self._pick(logits[0], req.temperature, req.first_key,
                            clamp=False)
        req.stream._emit(first0 + 1)
        self.metrics.record_first_token(
            req.stream.first_token_at - req.stream.submitted_at)
        if req.max_new == 1 or (req.eos0 is not None
                                and first0 == req.eos0):
            req.stream._finish()
            self.metrics.record_complete()
            self._trace_done(req.stream, req.rid)
            self.pool.release(blocks)
            with self._cv:
                self._free.append(slot)
            return
        if self.migrate is not None:
            # prefill-phase replica: the chain + sampling state hop to
            # a decode replica; this engine's slot and blocks free as
            # soon as the coordinator is done with them (the callback
            # runs with our references still held)
            h = KVHandoff(req, first0, self.name)
            try:
                with _tracer.span("lm/migrate", cat="serve",
                                  prompt_len=t,
                                  **({"request_id": req.rid}
                                     if _tracer.sampled(req.rid) else {})):
                    self.migrate(h, blocks, self.pool)
                self.migrated += 1
            except BaseException as e:  # noqa: BLE001
                req.stream._finish(error=e)
                self._trace_done(req.stream, req.rid)
            finally:
                self.pool.release(blocks)
                with self._cv:
                    self._free.append(slot)
            return
        self._seat(req, t, first0, blocks, slot)
        # (the windowed classes' marks go on with the sequence)
        self._slots[slot].marks = pf.marks

    def _seat(self, req: _Request, t: int, first0: int,
              blocks: List[int], slot: int) -> None:
        st = _Slot(req, t, first0, blocks,
                   self.pool.table(blocks, self.table_width))
        if self.draft is not None and not self._selfdraft:
            # drafter admission: full-prompt prefill into its dense
            # per-slot cache, first emitted token queued as pending.
            # Over-length (chunk-admitted) prompts serve plain decode.
            st.draft_ok = self.draft.can_draft(t)
            if st.draft_ok:
                self.draft.admit(slot, req.prompt0)
                self.draft.push(slot, first0)
                if self.spec.tree:
                    st.tree_rung = self.spec.init_rung
        with self._cv:
            self._slots[slot] = st
            self._n_active += 1

    def _dispatch(self, ahead: bool) -> _Round:
        """The first half of a plain decode round: build its operands
        from what the host knows WITHOUT the previous round's ids --
        positions, sampling keys, counts, tables (blocks are allotted
        whole at admission) -- advance those books and enqueue the step.
        ``ahead``: the previous round is still on the device (it is
        ``self._flying``), so a slot of it takes its token from that
        round's ids there (``TAKE_PREV``), and a slot whose count ends
        with it is not in this round.  The device runs the calls in
        dispatch order, which is all the ordering relied on.  At least
        one slot decodes (``_n_active``, ``_runs_ahead``)."""
        if self._selfdraft:
            return self._dispatch_selfdraft(ahead)
        rnd = _Round(self._stamp(P_DISPATCH), ahead)
        # one operand vector a round; what stays zero in it: an idle
        # slot, a greedy pick (no temperature, no key), nobody's blocks
        operands, token, pos, temperature, keys, live = decode_operands(
            self.slots, sum(self._live_entries))
        rows, chains = rnd.rows, [[] for _ in self.pool.classes]
        # the slots whose last token is a pick of the round on the device
        # (``last0`` is one behind until that round is collected)
        taken = ({i for i, _, emits, _ in self._flying.rows if emits}
                 if ahead else ())
        windows = self._windows
        if self.pool.windowed:
            # the windowed classes' releases and allotments of the round,
            # every slot's: host time of the round (round_host_ms)
            t_rel = time.perf_counter()
            for st in self._slots:
                if st is not None and st.remaining > 0 and (
                        st.pos_next >= st.marks["upto"]
                        or st.pos_next >= st.marks["pos"]):
                    self._advance(st, st.pos_next, st.pos_next + 1)
            if _tracer.enabled:
                _tracer.add_complete(
                    "lm/window_release", t_rel, time.perf_counter() - t_rel,
                    cat="serve", args={"round": self._rd_index})
        for i, st in enumerate(self._slots):
            if st is None or st.remaining <= 0:
                continue        # idle, or its last row is in flight
            token[i] = TAKE_PREV if i in taken else st.last0
            pos[i] = st.pos_next
            if st.temperature > 0.0 and st.step_keys is not None:
                temperature[i] = st.temperature
                keys[i] = st.step_keys[st.step_idx]
            # what the round reads of the slot's chain: the blocks up
            # to the one its new row is written to -- of a class with a
            # window, from the first that the window touches
            last = st.pos_next // self.block_len + 1
            for c, w in enumerate(windows):
                first = (0 if w is None else
                         max(0, st.pos_next - w + 1) // self.block_len)
                chains[c].append((i, st.table[c, first:last], first))
                rnd.n_live += last - first
                if w is None:
                    rnd.ctx_tokens += st.pos_next + 1
                else:
                    rnd.window_tokens += min(st.pos_next + 1, w)
                    rnd.window_held += last - st.marks[c]
                    rnd.window_spanned += last
                    rnd.window_held_max = max(rnd.window_held_max,
                                              last - st.marks[c])
            rnd.n_positions += st.pos_next + 1
            if _tracer.enabled and _tracer.sampled(st.rid):
                rnd.sampled.append((st.rid, i, st.step_idx))
            emits = not st.replay
            if not emits:
                # payload-less resume: this step rebuilds last0's KV
                # row; the next token was already emitted before
                # hibernation — take it from the replay queue instead
                # of the step's id (no re-emit, no ITL sample).  The
                # queue preserves the original step_keys alignment, so
                # post-replay sampling is bit-exact.
                st.last0 = st.replay.popleft()
            st.pos_next += 1
            st.step_idx += 1
            st.remaining -= 1
            last = emits and st.remaining <= 0
            rnd.n_last += last
            rows.append((i, st, emits, last))
        if not self._rd_active:     # (a round collected here names it)
            self._rd_active = len(rows)
        at = 0
        for c, n in enumerate(self._live_entries):
            live[:, at:at + n] = live_list(chains[c], n, self.slots)
            at += n
        # what the step gathers: the chunks that hold a listed block
        chunk = self._list_chunk
        rnd.gathered = -(-rnd.n_live // chunk) * chunk
        ids, *out = self._decode_compiled()(
            self._params, operands, self._ids, *self._arenas())
        rnd.ids = self._ids = ids
        ids.copy_to_host_async()
        if self._moe_layers:
            rnd.moe, *out = out
            rnd.moe.copy_to_host_async()    # lands with the ids: one wait
        if self.state is not None:
            *out, state, tail = out
            self.state.arenas = (state, tail)
        self.pool.arenas = out
        return rnd

    def _collect(self, rnd: _Round) -> None:
        """The second half: wait for the round's ids, emit them, take
        the ITL samples, finish and free.  A row whose slot no longer
        holds its stream is thrown away: the stream ended on its eos in
        the round before, while this one was on the device (the row
        wrote one position further into a block the stream still held
        when the step was enqueued; whatever reuses the block is
        enqueued behind that step)."""
        if self._selfdraft:
            return self._collect_selfdraft(rnd)
        self._stamp(P_WAIT)
        ids = np.asarray(rnd.ids)  # sync; (S,) int32
        moe = rnd.moe
        if moe is not None:
            moe = np.asarray(moe)
            self.metrics.record_moe(moe, self._moe_layers)
        now = self._stamp(P_EMIT)
        if rnd.ids is self._ids:    # no successor was enqueued: a drain
            self._proved_empty(now, "decode_wait")
        n_rows = self._rd_active = len(rnd.rows)
        state_rows = n_rows * self._state_layers
        # live positions the round's latent layers read, the new rows too
        latent_rows = rnd.n_positions * self._latent_layers
        # a round enqueued behind another was the device's from the
        # instant its predecessor's ids were out: consecutive spans abut
        t0 = max(rnd.t0, self._step_end)
        self._step_end = now
        if _tracer.enabled:
            step_args = {"active": n_rows, "round": self._rd_index,
                         "ahead": int(rnd.ahead),
                         "live_blocks": rnd.n_live,
                         "gather_blocks": rnd.gathered}
            if moe is not None:
                step_args.update(moe_assignments=int(moe[0]),
                                 moe_experts_hit=int(moe[1]),
                                 moe_row_tiles=int(moe[-1]))
            if self.state is not None:
                step_args.update(state_rows=state_rows,
                                 state_step_path=self._state_step_path)
            if moe is not None and len(moe) > 3:
                step_args["moe_groups_hit"] = int(moe[2])
            if self._latent_layers:
                step_args["latent_positions"] = latent_rows
            if self._classes:
                step_args.update(ctx_tokens=rnd.ctx_tokens,
                                 window_tokens=rnd.window_tokens)
            _tracer.add_complete("lm/decode_step", t0, now - t0, cat="serve",
                                 args=step_args)
            # per-request view of the shared batched step: one
            # retroactive span per sampled slot, all spanning [t0, now]
            for rid, i, step in rnd.sampled:
                _tracer.add_complete(
                    "lm/decode_round", t0, now - t0, cat="request",
                    args={"request_id": rid, "slot": i, "step": step})
        itls = []
        freed = []
        discarded = 0
        for i, st, emits, last in rnd.rows:
            if self._slots[i] is not st:
                discarded += 1
                continue
            if not emits:
                continue
            nxt0 = int(ids[i])
            st.stream._emit(nxt0 + 1)
            itls.append(now - st.last_emit_at)
            st.last_emit_at = now
            st.last0 = nxt0
            if last or (st.eos0 is not None and nxt0 == st.eos0):
                st.stream._finish()
                self.metrics.record_complete()
                freed.append(i)
        self.metrics.record_step(n_rows, itls,
                                 prefill_interrupted=self._prefill_since_step,
                                 live_blocks=rnd.n_live,
                                 gathered_blocks=rnd.gathered,
                                 state_rows=state_rows,
                                 latent_rows=latent_rows, ahead=rnd.ahead,
                                 discarded=discarded,
                                 ctx_tokens=rnd.ctx_tokens,
                                 window_tokens=rnd.window_tokens,
                                 window_held=rnd.window_held,
                                 window_spanned=rnd.window_spanned,
                                 window_held_max=rnd.window_held_max)
        self._prefill_since_step = False
        if freed:
            with self._cv:
                for i in freed:
                    st = self._slots[i]
                    self._trace_done(st.stream, st.rid)
                    self.pool.release(st.blocks)
                    self._slots[i] = None
                    self._free.append(i)
                    self._n_active -= 1
                # (a freed slot's rows of the state arena stay as they
                # are: the next admission into it overwrites them whole)
                self._cv.notify_all()

    def _step_spec(self):
        """One speculative round: draft k tokens per eligible slot, run
        the SINGLE fixed-shape verify executable over all k+1 candidate
        rows per slot, then walk each slot's rows host-side emitting
        the accepted prefix plus one bonus/correction token — the exact
        offline trajectory under "replay" acceptance.  Rejection is a
        pointer rewind: the slot simply doesn't advance past the last
        emitted position, and the arena rows above it stay masked until
        overwritten.  Demoted / chunk-admitted / budget-exhausted slots
        ride the same round as plain n_cand=1 rows."""
        from bigdl_tpu.resilience.faults import fault_point
        from bigdl_tpu.serving.spec.verify import accept_row

        cfg = self.spec
        if cfg.tree:
            return self._step_spec_tree()
        mode = cfg.sampling
        # -- choose who speculates this round --------------------------- #
        self._stamp(P_DRAFT)
        jobs = {}
        for i, st in enumerate(self._slots):
            if st is None or not st.draft_ok:
                continue
            if st.demoted:
                st.probe_in -= 1
                if st.probe_in > 0:
                    continue
                # re-probe: forget the collapsed EMA and try again
                st.demoted = False
                st.accept_ema = None
                st.spec_rounds = 0
                self.spec_metrics.record_reprobe()
            # never draft past the budget: the round emits at most
            # k_eff + 1 tokens, and every verify write must stay inside
            # the chain allocated for prompt + max_new at admission
            k_eff = min(cfg.k, st.remaining - 1)
            if k_eff < 1:
                continue
            keys = None
            if st.temperature > 0.0 and st.step_keys is not None:
                keys = st.step_keys[st.step_idx:st.step_idx + k_eff]
            jobs[i] = (k_eff, st.temperature, keys)
        steps_before = self.draft.steps
        drafts = self.draft.draft_round(jobs)

        # chaos hook on the verify step: an injected transient demotes
        # every speculating slot to plain decode for this round (their
        # drafts are discarded, the drafter pointer rewinds) instead of
        # killing streams; backend_lost/die keep their fatal meaning
        try:
            fault_point("serving.verify", name=self.name,
                        k=cfg.k, speculating=len(jobs))
        except TransientBackendError:
            for i in jobs:
                st = self._slots[i]
                self.draft.commit(i, 0, [])
                st.demoted = True
                st.probe_in = cfg.probe_interval
                self.spec_metrics.record_demotion(fault=True)
                if _tracer.sampled(st.rid):
                    _tracer.instant("lm/demote", cat="serve",
                                    request_id=st.rid, slot=i,
                                    reason="verify_fault")
            drafts = {}
            jobs = {}

        # -- one fixed-shape verify over every active slot -------------- #
        t0 = self._stamp(P_DISPATCH)
        w = cfg.k + 1
        tokens = np.zeros((self.slots, w), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        ncand = np.zeros((self.slots,), np.int32)
        tables = np.zeros(self._tables_shape(), np.int32)
        active = []
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            active.append(i)
            ds, _, _ = drafts.get(i, ((), None, ()))
            tokens[i, 0] = st.last0
            for j, d in enumerate(ds):
                tokens[i, 1 + j] = d
            ncand[i] = 1 + len(ds)
            pos[i] = st.pos_next
            # (a windowed class: the candidate rows' blocks are allotted,
            # what lies behind the first row's window goes)
            self._advance(st, st.pos_next, st.pos_next + 1 + len(ds))
            tables[..., i, :] = self._by_class(st.table)
        if not active:
            return
        self._rd_active = len(active)
        logits, *self.pool.arenas = self._verify_compiled()(
            self._params, tokens, pos, ncand, tables, *self.pool.arenas)
        self._stamp(P_WAIT)
        logits = np.asarray(logits)  # sync; (S, W, V) f32
        self.metrics.record_logit_rows(logits.shape[0] * logits.shape[1])
        now = self._stamp(P_EMIT)
        self._proved_empty(now, "decode_wait")  # a verify round is synchronous
        if _tracer.enabled:
            _tracer.add_complete(
                "lm/verify_step", t0, now - t0, cat="serve",
                args={"active": len(active), "speculating": len(jobs),
                      "round": self._rd_index})
            for i in active:
                st = self._slots[i]
                if _tracer.sampled(st.rid):
                    _tracer.add_complete(
                        "lm/verify_round", t0, now - t0, cat="request",
                        args={"request_id": st.rid, "slot": i,
                              "step": st.step_idx,
                              "speculating": i in jobs})
        itls = []
        freed = []
        n_emitted = 0
        for i in active:
            st = self._slots[i]
            if st.replay:
                # payload-less resume riding a spec round as a plain
                # n_cand=1 row: the verify kernel rebuilt last0's KV;
                # the next token replays instead of sampling (resumed
                # slots have draft_ok=False, so no draft state exists)
                st.last0 = st.replay.popleft()
                st.pos_next += 1
                st.step_idx += 1
                st.remaining -= 1
                continue
            ds, qrows, _ = drafts.get(i, ((), None, ()))
            k_eff = len(ds)
            emitted = []
            accepted = 0
            finished = False
            for j in range(k_eff + 1):
                key = (st.step_keys[st.step_idx]
                       if st.step_keys is not None else None)
                e = accept_row(logits[i, j],
                               ds[j] if j < k_eff else None,
                               st.temperature, key, mode,
                               qrows[j] if qrows is not None
                               and j < k_eff else None)
                emitted.append(e)
                st.stream._emit(e + 1)
                itls.append(now - st.last_emit_at)
                st.last_emit_at = now
                st.last0 = e
                st.pos_next += 1
                st.step_idx += 1
                st.remaining -= 1
                if st.remaining <= 0 or (st.eos0 is not None
                                         and e == st.eos0):
                    finished = True
                    break
                if j >= k_eff or ds[j] != e:
                    break
                accepted += 1
            n_emitted += len(emitted)
            if k_eff:
                self.spec_metrics.record_round(k_eff, accepted)
                rate = accepted / k_eff
                st.accept_ema = (rate if st.accept_ema is None
                                 else cfg.ema_alpha * rate
                                 + (1.0 - cfg.ema_alpha) * st.accept_ema)
                st.spec_rounds += 1
                if (not finished and st.spec_rounds >= cfg.min_rounds
                        and st.accept_ema < cfg.demote_below):
                    st.demoted = True
                    st.probe_in = cfg.probe_interval
                    self.spec_metrics.record_demotion()
                    if _tracer.sampled(st.rid):
                        _tracer.instant("lm/demote", cat="serve",
                                        request_id=st.rid, slot=i,
                                        reason="acceptance_collapse",
                                        accept_ema=round(st.accept_ema, 4))
            if finished:
                st.stream._finish()
                self.metrics.record_complete()
                freed.append(i)
            elif st.draft_ok:
                if k_eff:
                    self.draft.commit(i, accepted, emitted)
                else:
                    self.draft.push(i, emitted[0])
        self.spec_metrics.record_verify_round(
            bool(jobs), n_emitted, self.draft.steps - steps_before)
        self.metrics.record_step(len(active), itls,
                                 prefill_interrupted=self._prefill_since_step)
        self._prefill_since_step = False
        if freed:
            with self._cv:
                for i in freed:
                    st = self._slots[i]
                    self._trace_done(st.stream, st.rid)
                    self.pool.release(st.blocks)
                    self._slots[i] = None
                    if self.draft is not None:
                        self.draft.release(i)
                    self._free.append(i)
                    self._n_active -= 1
                self._cv.notify_all()

    def _dispatch_selfdraft(self, ahead: bool) -> _Round:
        """The first half of a SELF-DRAFTING round: the model's own
        prediction module is the drafter, through the target's pool, and
        ONE program verifies, picks and drafts
        (``generate._selfdraft_step_paged``).  A slot hands it its last
        emitted token and the module's draft for the position after (none
        in the round that follows its prefill, or where one token is left
        of its count: a plain row); the program hands back ``(S, 4)`` ids
        -- the picks of both rows, whether the draft was the first pick,
        and the next round's draft -- so a slot advances by 1 or 2 and the
        host sees ids and counts, never logits.  A rejected draft is a
        pointer rewind, in the main layers' rows and the module's alike.

        ``ahead``: the previous round is still on the device
        (``self._flying``), and what it accepted has not reached the
        host.  A slot of it is CHAINED: the host hands over its position
        and count as of before that round, and the step takes its tokens,
        how far it advanced and whether it verifies a draft from that
        round's output on the device (``split_selfdraft_operands``); its
        live list reaches two positions further, the most it can have
        advanced.  A slot that may have ended with the round in flight
        (two tokens or fewer were left of its count) sits this round
        out."""
        rnd = _Round(self._stamp(P_DISPATCH), ahead)
        B = self.block_len
        (operands, tokens, pos, ncand, fresh, temperature, keys, live, chain,
         remaining) = selfdraft_operands(self.slots,
                                         self.slots * self.table_width)
        flying = {i for i, _, _ in self._flying.rows} if ahead else ()
        rows, chains = rnd.rows, []
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            pos[i] = st.pos_next
            if i in flying:
                if st.remaining <= 2:
                    continue
                chain[i], remaining[i], cand = 1, st.remaining, None
                reach = st.pos_next + 4
            else:
                tokens[i, 0] = st.last0
                fresh[i] = st.fresh
                ncand[i] = cand = 1
                if st.draft is not None and st.remaining >= 2:
                    tokens[i, 1] = st.draft
                    ncand[i] = cand = 2
                if st.temperature > 0.0 and st.step_keys is not None:
                    temperature[i] = st.temperature
                    ks = st.step_keys[st.step_idx:st.step_idx + 4]
                    keys[i, :len(ks)] = ks
                reach = st.pos_next + 2
            # what the round reads and writes of the slot's chain: up to the
            # block of the module's furthest row
            held = st.table[0, :min(reach // B + 1, len(st.blocks))]
            chains.append((i, held))
            rnd.n_live += len(held)
            rows.append((i, st, cand))
        if not self._rd_active:     # (a round collected here names it)
            self._rd_active = len(rows)
        live[:] = live_list(chains, live.shape[1], self.slots)
        chunk = self._list_chunk
        rnd.gathered = -(-rnd.n_live // chunk) * chunk
        out, *rest = self._verify_compiled()(
            self._params, operands, self._hid, self._prev_out,
            *self.pool.arenas)
        rnd.ids = self._prev_out = out
        out.copy_to_host_async()
        if self._round_moe_layers:
            rnd.moe, *rest = rest
            rnd.moe.copy_to_host_async()    # lands with the ids: one wait
        self.pool.arenas = rest
        return rnd

    def _collect_selfdraft(self, rnd: _Round) -> None:
        """The second half: wait for the round's ``(S, 4)`` ids and counts,
        emit one or two tokens a slot, finish and free.  A chained row's
        ``n_cand`` is the step's own rule over what the host knows by now;
        a row whose slot no longer holds its stream is thrown away (the
        stream ended on its eos in the round before: ``_collect``)."""
        self._stamp(P_WAIT)
        out = np.asarray(rnd.ids)       # sync; (S, 4) int32: ids and counts
        moe = rnd.moe
        if moe is not None:
            moe = np.asarray(moe)
            self.metrics.record_moe(moe, self._round_moe_layers)
        now = self._stamp(P_EMIT)
        if rnd.ids is self._prev_out:   # no successor was enqueued: a drain
            self._proved_empty(now, "decode_wait")
        self._rd_active = len(rnd.rows)
        t0 = max(rnd.t0, self._step_end)
        self._step_end = now
        itls, freed = [], []
        n_active = n_positions = drafted = discarded = 0
        n_emitted = accepted = pairs = 0
        for i, st, cand in rnd.rows:
            if self._slots[i] is not st:
                discarded += 1
                continue
            if cand is None:
                cand = 2 if st.remaining >= 2 else 1
            y0, y1, acc, nxt = (int(v) for v in out[i])
            n_active += 1
            n_positions += st.pos_next + cand
            if cand == 2:
                st.stream.drafts.append((len(st.stream._tokens),
                                         int(st.draft) + 1))
                self.spec_metrics.record_round(1, acc)
                drafted += 1
                accepted += acc
            pairs += 2 if st.fresh else 1 + acc
            finished = False
            for e in (y0, y1)[:1 + acc]:
                st.stream._emit(e + 1)
                itls.append(now - st.last_emit_at)
                st.last_emit_at = now
                st.last0 = e
                st.pos_next += 1
                st.step_idx += 1
                st.remaining -= 1
                n_emitted += 1
                if st.remaining <= 0 or (st.eos0 is not None
                                         and e == st.eos0):
                    finished = True
                    break
            st.draft, st.fresh = nxt, False
            if finished:
                st.stream._finish()
                self.metrics.record_complete()
                freed.append(i)
        self.draft.steps += pairs
        # (position, layer) rows read: every arena layer's, the module's too
        layers = self._latent_layers + 1
        self.spec_metrics.record_verify_round(
            bool(drafted), n_emitted, pairs, draft_rows=n_positions)
        self.metrics.record_step(
            n_active, itls, prefill_interrupted=self._prefill_since_step,
            live_blocks=rnd.n_live, gathered_blocks=rnd.gathered,
            latent_rows=n_positions * layers, ahead=rnd.ahead,
            discarded=discarded)
        self._prefill_since_step = False
        if _tracer.enabled:
            args = {"active": n_active, "round": self._rd_index,
                    "ahead": int(rnd.ahead),
                    "speculating": drafted, "live_blocks": rnd.n_live,
                    "gather_blocks": rnd.gathered,
                    "latent_positions": n_positions * layers,
                    "drafted": drafted, "accepted": accepted,
                    "emitted": n_emitted}
            if moe is not None:
                args.update(moe_assignments=int(moe[0]),
                            moe_experts_hit=int(moe[1]),
                            moe_row_tiles=int(moe[-1]))
            _tracer.add_complete("lm/verify_step", t0, now - t0,
                                 cat="serve", args=args)
            # the drafter ran inside the same program: a marker, no phase
            _tracer.add_complete("lm/draft", now, 0.0, cat="serve",
                                 args={"fused": 1, "pairs": pairs,
                                       "round": self._rd_index})
        if freed:
            with self._cv:
                for i in freed:
                    st = self._slots[i]
                    self._trace_done(st.stream, st.rid)
                    self.pool.release(st.blocks)
                    self._slots[i] = None
                    self._free.append(i)
                    self._n_active -= 1
                self._cv.notify_all()

    def _step_spec_tree(self):
        """One TREE-speculative round (replay acceptance only): each
        eligible slot picks a ladder rung (its adaptive ``tree_rung``,
        clamped down so the shape fits its remaining budget), the
        drafter proposes the spine plus ranked runner-up alternates at
        zero extra steps, and ONE pre-lowered verify executable — the
        round's widest participating rung, narrower slots truncated via
        ``n_cand`` — scores every node against the paged arenas.  The
        host then walks each slot's tree root-down, emitting the offline
        ``pick_token`` draw at every accepted node, so the stream is the
        exact offline trajectory whichever branch carried it.  A slot
        that accepted an ALTERNATE has that node's k/v committed down to
        its position offset afterwards (``_tree_commit_paged``, skipped
        entirely on spine-only rounds); rejected rows stay as masked
        garbage above the rewound pointer, same as linear verify.

        The acceptance EMA drives three nested responses: rung
        promotion at ``promote_above`` (speculate deeper/wider), rung
        step-down at ``stepdown_below``, and full demotion to plain
        decode below ``demote_below`` with the same re-probe lifecycle
        as linear mode — a re-probed slot restarts at ``init_rung``."""
        from bigdl_tpu.resilience.faults import fault_point
        from bigdl_tpu.serving.spec.verify import pick_token

        cfg = self.spec
        shapes = self._tree_shapes
        top = len(shapes) - 1
        # -- choose who speculates, and at which rung ------------------- #
        self._stamp(P_DRAFT)
        jobs: dict = {}
        for i, st in enumerate(self._slots):
            if st is None or not st.draft_ok:
                continue
            if st.demoted:
                st.probe_in -= 1
                if st.probe_in > 0:
                    continue
                # re-probe: forget the collapsed EMA, restart the ladder
                st.demoted = False
                st.accept_ema = None
                st.spec_rounds = 0
                st.tree_rung = cfg.init_rung
                self.spec_metrics.record_reprobe()
            # budget clamp: the shape stores nodes at pos .. pos+W-1 and
            # emits at most max_depth+1 <= W tokens, so W <= remaining
            # keeps every write and every emission inside the chain
            # allocated at admission
            rung = min(st.tree_rung, top)
            while rung >= 0 and shapes[rung].width > st.remaining:
                rung -= 1
            if rung < 0:
                continue        # remaining == 1: ride as a plain row
            jobs[i] = rung
        djobs = {}
        for i, rung in jobs.items():
            st = self._slots[i]
            shp = shapes[rung]
            keys = None
            if st.temperature > 0.0 and st.step_keys is not None:
                keys = st.step_keys[st.step_idx:st.step_idx + shp.spine]
            djobs[i] = (shp.spine, st.temperature, keys, shp.alt_counts)
        steps_before = self.draft.steps
        drafts = self.draft.draft_round(djobs)

        # same chaos site as linear verify — tree rounds demote
        # identically: drafts discarded, round served plain, streams
        # stay bit-exact
        try:
            fault_point("serving.verify", name=self.name,
                        k=cfg.k, speculating=len(jobs), tree=True)
        except TransientBackendError:
            for i in jobs:
                st = self._slots[i]
                self.draft.commit(i, 0, [])
                st.demoted = True
                st.probe_in = cfg.probe_interval
                self.spec_metrics.record_demotion(fault=True)
                if _tracer.sampled(st.rid):
                    _tracer.instant("lm/demote", cat="serve",
                                    request_id=st.rid, slot=i,
                                    reason="verify_fault")
            drafts = {}
            jobs = {}

        # -- one verify at the round's widest rung ---------------------- #
        t0 = self._stamp(P_DISPATCH)
        round_rung = max(jobs.values(), default=0)
        shp_round = shapes[round_rung]
        w = shp_round.width
        tokens = np.zeros((self.slots, w), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        ncand = np.zeros((self.slots,), np.int32)
        tables = np.zeros((self.slots, self.table_width), np.int32)
        active = []
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            active.append(i)
            tokens[i, 0] = st.last0
            pos[i] = st.pos_next
            tables[i] = st.table[0]
            if i in jobs:
                shp = shapes[jobs[i]]
                ds, _, alts = drafts[i]
                for j in range(1, shp.width):
                    p = shp.parents[j]
                    if j <= shp.spine:
                        tokens[i, j] = ds[j - 1]
                    else:
                        ranked = alts[p] if p < len(alts) else ()
                        r = shp.alt_rank[j]
                        # an unfillable alternate keeps token 0: under
                        # replay it accepts only if 0 IS the offline
                        # emission, which is a legitimate accept
                        if r < len(ranked):
                            tokens[i, j] = ranked[r]
                ncand[i] = shp.width
            else:
                ncand[i] = 1
        if not active:
            return
        self._rd_active = len(active)
        logits, *self.pool.arenas = self._verify_tree_compiled(round_rung)(
            self._params, tokens, pos, ncand, tables, *self.pool.arenas)
        self._stamp(P_WAIT)
        logits = np.asarray(logits)  # sync; (S, W, V) f32
        self.metrics.record_logit_rows(logits.shape[0] * logits.shape[1])
        now = self._stamp(P_EMIT)
        self._proved_empty(now, "decode_wait")  # a verify round is synchronous
        if _tracer.enabled:
            _tracer.add_complete(
                "lm/verify_step", t0, now - t0, cat="serve",
                args={"active": len(active), "speculating": len(jobs),
                      "tree_w": w, "round": self._rd_index})
            for i in active:
                st = self._slots[i]
                if _tracer.sampled(st.rid):
                    _tracer.add_complete(
                        "lm/verify_round", t0, now - t0, cat="request",
                        args={"request_id": st.rid, "slot": i,
                              "step": st.step_idx,
                              "speculating": i in jobs})
        itls = []
        freed = []
        n_emitted = 0
        commit_src = None     # lazily built: only alternate accepts move
        for i in active:
            st = self._slots[i]
            if st.replay:
                # payload-less resume riding the round as a plain row
                st.last0 = st.replay.popleft()
                st.pos_next += 1
                st.step_idx += 1
                st.remaining -= 1
                continue
            shp = shapes[jobs[i]] if i in jobs else None
            emitted = []
            node = 0
            accepted = 0
            spine_ok = 0
            alt_ok = 0
            finished = False
            while True:
                key = (st.step_keys[st.step_idx]
                       if st.step_keys is not None else None)
                e = pick_token(logits[i, node], st.temperature, key,
                               clamp=True)
                emitted.append(e)
                st.stream._emit(e + 1)
                itls.append(now - st.last_emit_at)
                st.last_emit_at = now
                st.last0 = e
                st.pos_next += 1
                st.step_idx += 1
                st.remaining -= 1
                if st.remaining <= 0 or (st.eos0 is not None
                                         and e == st.eos0):
                    finished = True
                    break
                nxt = None
                if shp is not None:
                    for c in shp.children[node]:
                        if int(tokens[i, c]) == e:
                            nxt = c
                            break
                if nxt is None:
                    break
                accepted += 1
                if nxt <= shp.spine:
                    spine_ok += 1
                else:
                    # the accepted path left the spine: schedule this
                    # node's k/v copy-down (alternates are leaves, so at
                    # most one move per slot per round)
                    alt_ok += 1
                    if commit_src is None:
                        commit_src = np.tile(
                            np.arange(1, self._commit_dmax + 1,
                                      dtype=np.int32),
                            (self.slots, 1))
                    commit_src[i, accepted - 1] = nxt
                node = nxt
            n_emitted += len(emitted)
            if shp is not None:
                self.spec_metrics.record_round(shp.width - 1, accepted)
                self.spec_metrics.record_tree_slot(
                    shp.max_depth, shp.width, len(emitted), alt_ok)
                rate = accepted / shp.max_depth
                st.accept_ema = (rate if st.accept_ema is None
                                 else cfg.ema_alpha * rate
                                 + (1.0 - cfg.ema_alpha) * st.accept_ema)
                st.spec_rounds += 1
                if (not finished and st.spec_rounds >= cfg.min_rounds
                        and st.accept_ema < cfg.demote_below):
                    st.demoted = True
                    st.probe_in = cfg.probe_interval
                    self.spec_metrics.record_demotion()
                    if _tracer.sampled(st.rid):
                        _tracer.instant("lm/demote", cat="serve",
                                        request_id=st.rid, slot=i,
                                        reason="acceptance_collapse",
                                        accept_ema=round(st.accept_ema, 4))
                elif st.accept_ema >= cfg.promote_above:
                    st.tree_rung = min(st.tree_rung + 1, top)
                elif st.accept_ema < cfg.stepdown_below:
                    st.tree_rung = max(st.tree_rung - 1, 0)
            if finished:
                st.stream._finish()
                self.metrics.record_complete()
                freed.append(i)
            elif st.draft_ok:
                if shp is not None:
                    # the drafter's cache tracks only the SPINE: rewind
                    # past accepted spine drafts, catch up on the rest
                    self.draft.commit(i, spine_ok, emitted)
                else:
                    self.draft.push(i, emitted[0])
        if commit_src is not None:
            self._stamp(P_TREE_COMMIT)
            self.pool.arenas = self._commit_compiled()(
                commit_src, pos, tables, *self.pool.arenas)
            self._stamp(P_EMIT)
        self.spec_metrics.record_verify_round(
            bool(jobs), n_emitted, self.draft.steps - steps_before)
        self.metrics.record_step(len(active), itls,
                                 prefill_interrupted=self._prefill_since_step)
        self._prefill_since_step = False
        if freed:
            with self._cv:
                for i in freed:
                    st = self._slots[i]
                    self._trace_done(st.stream, st.rid)
                    self.pool.release(st.blocks)
                    self._slots[i] = None
                    if self.draft is not None:
                        self.draft.release(i)
                    self._free.append(i)
                    self._n_active -= 1
                self._cv.notify_all()

    def _fail_all(self, error: BaseException) -> None:
        with self._cv:
            pending = [r.stream for r in self._queue]
            self._queue.clear()
            pending.extend(h.stream for h in self._adopt_q)
            for h in self._adopt_q:
                if h.matched:
                    self.pool.release(h.matched)
            self._adopt_q.clear()
            for pf in self._prefilling:
                pending.append(pf.req.stream)
                self.pool.release(pf.blocks)
                self._free.append(pf.slot)
            self._prefilling.clear()
            for i, st in enumerate(self._slots):
                if st is not None:
                    pending.append(st.stream)
                    self.pool.release(st.blocks)
                    self._slots[i] = None
                    self._free.append(i)
            self._n_active = 0
            self._flying = None     # a round on the device is dropped
            if self.draft is not None:
                self.draft.release_all()
            # hibernated / resuming streams hold no pool blocks (their
            # chains live in the host tier), but their clients are
            # still waiting — resolve them too
            pending.extend(h.stream for h in self._hibernated.values())
            self._hibernated.clear()
            pending.extend(h.stream for h in self._resume_q)
            self._resume_q.clear()
            self._hibernate_req.clear()
            self._cv.notify_all()
        for s in pending:
            s._finish(error=error)

    # ------------------------------------------------------------------ #
    def kvcache_stats(self) -> dict:
        """Pool + radix state, for stats() and headroom checks."""
        out = self.pool.stats()
        out["table_width"] = self.table_width
        out["prefix_cache"] = (self.radix.stats()
                               if self.radix is not None else None)
        return out

    def chain_of(self, stream: LMStream) -> Optional[List[int]]:
        """The pool blocks a SEATED stream's cached rows lie in, in chain
        order (position ``p`` is row ``p % block_len`` of block
        ``chain[p // block_len]``); None when it holds no decode slot.  What
        a check reads the arenas by (``BlockPool.rows_at``) once the engine
        has closed: an ended stream's rows stay where they lay until a later
        stream's are written over them."""
        with self._cv:
            for st in self._slots:
                if st is not None and st.stream is stream:
                    return list(st.blocks)
        return None

    def kvcache_headroom(self) -> int:
        """How many additional WORST-CASE requests (a full
        ``cache_len`` context each) the pool can hold right now.  The
        SLO controller's scale-up check gates on this so added decode
        slots are backed by cache memory, not just scheduler entries."""
        return self.pool.free_count // self.table_width

    def stats(self) -> dict:
        with self._cv:
            queued = len(self._queue)
            active = self._n_active
            slot_limit = self._slot_limit
            max_queue = self._max_queue
            prefilling = len(self._prefilling)
            adopt_q = len(self._adopt_q)
            hibernated = len(self._hibernated)
        metrics = self.metrics.snapshot()
        return {
            "name": self.name,
            "slots": self.slots,
            "slot_limit": slot_limit,
            "max_queue": max_queue,
            "active": active,
            "queued": queued,
            "phase": self.phase,
            "prefilling": prefilling,
            "adopt_queue": adopt_q,
            "max_prefill_chunk_tokens": self._chunk_cap,
            "migrated": self.migrated,
            "adopted": self.adopted,
            "re_prefills": self.re_prefills,
            "cache_len": self.cache_len,
            "block_len": self.block_len,
            "decode_attn": self.decode_attn,
            "expert_matmul": self._expert_matmul_paths(),
            "placement": (self.placement.describe()
                          if self.placement is not None else None),
            "prefill_buckets": list(self.prefill_buckets),
            "prefill_cache": self.prefill_cache.stats(),
            "prefix_prefill_cache": self.prefix_prefill_cache.stats(),
            "kvcache": self.kvcache_stats(),
            # what a cached row is for the model at hand, and the pool's books
            "kv_pool": self.pool.stats(),
            # ... a class of blocks a kind of softmax layer: its layers (of
            # the plan), a position's lanes (keys + values, unpadded), its
            # window, its blocks and bytes
            "kv_classes": [dict(c, layers=list(k.layers))
                           for c, k in zip(self.pool.stats()["classes"],
                                           self._classes)],
            "latent_cache": ({"layers": self._latent_layers,
                              "row_bytes": self.pool.row_bytes,
                              "blocks_used": self.pool.used_count,
                              "blocks_free": self.pool.free_count,
                              **metrics["latent"]}
                             if self.pool.latent else None),
            "prefix_cache": self._prefix_cache_note,
            "prefix_tokens": metrics["prefix"],
            "state": ({"layers": self._state_layers,
                       "row_bytes": self.state.row_bytes,
                       "step_path": self._state_step_path, **metrics["state"]}
                      if self.state is not None else None),
            "kvtier": (self.kvtier.stats()
                       if self.kvtier is not None else None),
            "radix_summary": (self.radix_summary.stats()
                              if self.radix_summary is not None else None),
            "hibernated": hibernated,
            "hibernations": self.hibernations,
            "resumes": self.resumes,
            "resume_re_prefills": self.resume_re_prefills,
            "lifecycle": self.lifecycle_stats(),
            "metrics": metrics,
            "rounds": self.rounds_stats(),
            "spec": self._spec_stats(),
        }

    def rounds_stats(self) -> dict:
        """The always-on round record (``LMMetrics.rounds_snapshot``):
        rounds counted, seconds by phase, the running median of the
        plain rounds and the longest round with its own phase split --
        beside what the two witnesses of a stall saw: events the trace
        ring dropped, and the round watchdog's count."""
        out = self.metrics.rounds_snapshot()
        out["trace_dropped"] = _tracer.dropped
        if self.watchdog is not None:
            out["watchdog"] = {"stalls": self.watchdog.stall_count,
                               "median_round_s": self.watchdog.median()}
        return out

    def lifecycle_stats(self) -> dict:
        with self._lc_lock:
            return dict(self.lifecycle)

    def _expert_matmul_paths(self) -> Optional[dict]:
        """Which grouped matmul the routed layers of each step program take
        (``parallel.expert.expert_matmul_path``, asked what the program's
        trace asks: the rows are the program's tokens x top_k)."""
        moe = self.model.moe
        if moe is None or not self._round_moe_layers:
            return None
        from bigdl_tpu.parallel.expert import expert_matmul_path

        def path(tokens):
            return expert_matmul_path(
                tokens * moe.top_k, self.model.hidden_size, moe.width,
                self._params["embed"].dtype)

        out = {"decode": path(self.slots)}
        if self.spec is not None:
            out["verify"] = path(self.slots * (self.spec.k + 1))
        out.update({f"prefill_{b}": path(b) for b in self.prefill_buckets})
        return out

    def _spec_stats(self) -> Optional[dict]:
        if self.spec is None:
            return None
        with self._cv:
            demoted = sum(1 for s in self._slots
                          if s is not None and s.demoted)
        out = self.spec.describe()
        out["demoted_slots"] = demoted
        out["draft"] = self.draft.describe()
        # which of the three drafters serves, and whether through the
        # target's own pool
        out["drafter"] = ("prediction module" if self._selfdraft else
                          "ngram" if self.draft.compute_mode == "ngram"
                          else "draft model")
        out["shares_pool"] = self._selfdraft
        out["verify_compiles"] = self._verify_compiles
        if self.spec.tree:
            out["commit_compiles"] = self._commit_compiles
            with self._cv:
                out["slot_rungs"] = [s.tree_rung if s is not None else None
                                     for s in self._slots]
        out.update(self.spec_metrics.snapshot())
        return out

    def cache_buffer_pointers(self) -> tuple:
        """Device buffer addresses of the resident k/v arenas (donation
        regression hook: stable across decode steps)."""

        def ptr(a):
            try:
                return a.unsafe_buffer_pointer()
            except AttributeError:
                bufs = getattr(a, "device_buffers", None)
                return bufs[0].unsafe_buffer_pointer() if bufs else None

        return ptr(self.pool.k), ptr(self.pool.v)

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Drain: stop admitting, finish queued + in-flight requests;
        after ``timeout`` the remainder resolve with ServingClosed."""
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        self._worker.join(timeout)
        if self._worker.is_alive():
            with self._cv:
                self._abort = True
                self._cv.notify_all()
            self._worker.join(5.0)
            self._fail_all(ServingClosed("engine closed before "
                                         "completion"))
        if self.watchdog is not None:
            self.watchdog.reset()
            self.watchdog.stop()
        # drop this engine's memory-ledger attributions (the weakref
        # providers would go stale anyway; explicit release keeps the
        # table clean for the next engine)
        try:
            from bigdl_tpu.obs.ledger import get_ledger
            led = get_ledger()
            for sub, nm in getattr(self, "_ledger_keys", []):
                led.release(sub, nm)
        except Exception:
            pass

    def __enter__(self) -> "LMServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
