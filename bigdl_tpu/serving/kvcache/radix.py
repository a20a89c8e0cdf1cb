"""Radix prefix cache: token-prefix trie over refcounted block chains.

Chat-style production traffic repeats prompt heads constantly (system
prompts, few-shot preambles, multi-turn history).  The SGLang insight
(RadixAttention, 2023) is that a paged KV cache already stores every
prompt's k/v in shareable units — so keep a trie from token prefixes to
block chains, and admission can reuse the longest cached prefix
copy-free, prefilling only the unmatched suffix.

The trie here is **block-granular**: one node per full block of
``block_len`` tokens (the node key is that block's token tuple), so a
match is always a whole number of blocks and the reused chain can be
handed straight to the fixed-shape block-table programs.  Matching is
capped at ``(t - 1) // block_len`` blocks — the final prompt token is
always prefilled so the request has logits to sample its first token
from, exactly like a cold prefill.

Reference protocol (one pool refcount per holder):

- ``match`` retains every matched block on behalf of the caller (the
  admitted sequence); the caller releases them with the rest of its
  table when the stream finishes.
- ``insert`` retains each block it adopts into a NEW node.  A prompt
  whose prefix already exists in the trie keeps its duplicate private
  blocks — the trie never swaps a live sequence's storage.
- ``evict`` releases blocks whose ONLY reference is the trie itself
  (refcount 1), LRU-first, leaves-first — a chain referenced by any
  live sequence can never evict, and interior nodes only become
  candidates once their subtree is gone.

Every eviction funnels through ONE path: the optional ``on_evict``
callback fires per victim with ``(path, block)`` — ``path`` being the
tuple of block token-keys from the root down to the victim — BEFORE
the block is released, so a demotion hook (the host KV tier), a plain
drop, and test instrumentation all observe the identical sequence of
events.  The block is still allocated while the callback runs (its
k/v rows are gatherable); a callback that raises is logged and the
eviction proceeds — a flaky demotion target must not wedge the pool.

A pool of SEVERAL CLASSES of blocks (``kvcache.blocks``): a node's block is
the chain ENTRY -- one block id a class -- and the trie holds a reference in
every class, hands all of them out on a match and frees them together.  A
live sequence that lets go of what lies behind a class's window drops its
OWN reference alone, so a hit beside such a donor reads what a cold prefill
would write; an entry the inserting sequence has already let go of (a chunked
prefill's earlier blocks) cannot be adopted, and ``insert`` stops at the
first such entry.

Thread model: the serving worker is the only mutator; counters are
lock-guarded so stats/metrics reads from other threads are consistent.
Eviction walks the trie once a call (trie size is bounded by the pool's
block count).
"""
from __future__ import annotations

import logging
import threading
from typing import Callable, Dict, List, Optional, Tuple

from bigdl_tpu.serving.kvcache.blocks import BlockPool

log = logging.getLogger("bigdl_tpu.serving")

# Prefix fingerprints: every trie node carries a 64-bit FNV-1a chain
# hash of its full root->node block-key path.  The router's per-replica
# summary is just the SET of these sigs — membership of sig_i means "a
# chain covering blocks [0, i] of some prompt is cached here" — so a
# foreign router can measure longest-prefix overlap without walking (or
# even seeing) the trie.  The hash is deterministic across processes
# (no PYTHONHASHSEED dependence: plain int arithmetic).
_SIG_ROOT = 0xCBF29CE484222325     # FNV-1a 64-bit offset basis
_FNV_PRIME = 0x100000001B3
_U64 = (1 << 64) - 1


def _sig_extend(sig: int, key: Tuple[int, ...]) -> int:
    """Fold one block's token tuple into a cumulative prefix sig.
    Block keys have fixed length (``block_len``), so the chain hash is
    unambiguous without separators."""
    h = sig
    for tok in key:
        h = ((h ^ (int(tok) & _U64)) * _FNV_PRIME) & _U64
    return h


def prefix_signatures(tokens0, block_len: int,
                      cap: Optional[int] = None) -> List[int]:
    """Cumulative block-prefix sigs for a prompt (0-based ids):
    ``out[i]`` fingerprints blocks ``[0, i]``.  ``cap`` defaults to the
    same ``(t - 1) // block_len`` bound :meth:`RadixCache.match` uses —
    the last prompt token is always prefilled, never matched."""
    t = len(tokens0)
    n = max(0, (t - 1) // block_len)
    if cap is not None:
        n = min(n, int(cap))
    out: List[int] = []
    sig = _SIG_ROOT
    for i in range(n):
        key = tuple(int(x) for x in tokens0[i * block_len:
                                            (i + 1) * block_len])
        sig = _sig_extend(sig, key)
        out.append(sig)
    return out


class _Node:
    __slots__ = ("key", "block", "children", "parent", "last_used", "sig")

    def __init__(self, key: Optional[Tuple[int, ...]], block: Optional[int],
                 parent: Optional["_Node"], last_used: int,
                 sig: int = _SIG_ROOT):
        self.key = key
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.parent = parent
        self.last_used = last_used
        self.sig = sig


class RadixCache:
    """Longest-prefix block reuse over a :class:`BlockPool`."""

    def __init__(self, pool: BlockPool,
                 on_evict: Optional[Callable[[Tuple[Tuple[int, ...], ...],
                                              int], None]] = None):
        self.pool = pool
        self.block_len = pool.block_len
        #: the single eviction funnel: called as ``on_evict(path,
        #: block)`` per victim, before release, while the block is
        #: still allocated.  Reassignable live (the engine wires the
        #: host-tier demotion hook here).
        self.on_evict = on_evict
        #: optional router summary observer: ``on_insert(sig)`` /
        #: ``on_evict(sig)`` fire synchronously under the trie lock on
        #: every node add/drop, so the summary can never claim a chain
        #: the trie just evicted (the router-staleness hazard).  Wire it
        #: with :meth:`attach_summary`; independent of the block-level
        #: ``on_evict`` demotion funnel above.
        self.summary = None
        self._lock = threading.Lock()
        self._root = _Node(None, None, None, 0)
        self._clock = 0
        self.nodes = 0
        self.lookups = 0
        self.hits = 0
        self.matched_tokens = 0   # == prefill tokens saved
        self.inserted_blocks = 0
        self.evictions = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _block_key(self, tokens0, i: int) -> Tuple[int, ...]:
        B = self.block_len
        return tuple(int(x) for x in tokens0[i * B:(i + 1) * B])

    # -- lookup ---------------------------------------------------------- #
    def match(self, tokens0) -> List[int]:
        """Longest cached prefix of ``tokens0`` (0-based token ids), in
        whole blocks, capped so at least the last prompt token is left
        to prefill.  Matched blocks are retained for the caller."""
        t = len(tokens0)
        cap = max(0, (t - 1) // self.block_len)
        out: List[int] = []
        with self._lock:
            self.lookups += 1
            node = self._root
            now = self._tick()
            for i in range(cap):
                child = node.children.get(self._block_key(tokens0, i))
                if child is None:
                    break
                child.last_used = now
                out.append(child.block)
                node = child
            if out:
                self.hits += 1
                self.matched_tokens += len(out) * self.block_len
                self.pool.retain(out)
        return out

    # -- admission ------------------------------------------------------- #
    def insert(self, tokens0, blocks: List[int]) -> int:
        """Register a prefilled chain: ``blocks[i]`` holds tokens
        ``[i*B, (i+1)*B)`` of ``tokens0``.  Existing nodes are kept
        (their blocks stay authoritative; the caller's duplicates stay
        private to it); new tails are adopted with one trie reference.
        Returns the number of nodes added."""
        added = 0
        with self._lock:
            node = self._root
            now = self._tick()
            for i, blk in enumerate(blocks):
                key = self._block_key(tokens0, i)
                child = node.children.get(key)
                if child is None:
                    if not self.pool.whole(blk):
                        break   # let go behind a window: nothing to adopt
                    blk = blk if isinstance(blk, tuple) else int(blk)
                    child = _Node(key, blk, node, now,
                                  sig=_sig_extend(node.sig, key))
                    node.children[key] = child
                    self.pool.retain([blk])
                    self.nodes += 1
                    self.inserted_blocks += 1
                    added += 1
                    if self.summary is not None:
                        self.summary.on_insert(child.sig)
                else:
                    child.last_used = now
                node = child
        return added

    # -- eviction -------------------------------------------------------- #
    def _leaves(self) -> List[_Node]:
        out, stack = [], list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    @staticmethod
    def _path_of(node: _Node) -> Tuple[Tuple[int, ...], ...]:
        """Block token-keys from the root down to ``node`` — the
        tier-store identity of the node's block (content-addressed by
        its full prefix, so a demoted block is re-findable by any
        future prompt sharing that prefix)."""
        keys: List[Tuple[int, ...]] = []
        while node.key is not None:
            keys.append(node.key)
            node = node.parent
        return tuple(reversed(keys))

    def _evict_node(self, v: _Node) -> None:
        """THE eviction path — every drop goes through here.  Fires
        ``on_evict`` (demotion hook / instrumentation) while the block
        is still allocated, then releases the trie's reference."""
        hook = self.on_evict
        if hook is not None:
            try:
                hook(self._path_of(v), v.block)
            except Exception:  # noqa: BLE001 — a failing demotion
                # target degrades the eviction to a plain drop
                log.exception("radix on_evict hook failed; dropping "
                              "block %s", v.block)
        del v.parent.children[v.key]
        self.pool.release([v.block])
        self.nodes -= 1
        self.evictions += 1
        if self.summary is not None:
            self.summary.on_evict(v.sig)

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` pool blocks by dropping LRU leaf
        nodes whose block has no holder but the trie (refcount 1).
        Returns how many blocks were actually freed.  ONE walk of the trie a
        call: the leaves in a heap by age, a parent joining it when its last
        child goes (an admission that is short of a windowed class's blocks
        evicts some tens at a time from a trie of thousands of nodes)."""
        import heapq
        target = max(1, int(n_blocks))
        freed = 0
        with self._lock:
            heap = [(n.last_used, id(n), n) for n in self._leaves()]
            heapq.heapify(heap)
            while freed < target and heap:
                _, _, v = heapq.heappop(heap)
                if v.children or self.pool.refcount(v.block) != 1:
                    continue
                parent = v.parent
                self._evict_node(v)
                freed += 1
                if parent is not self._root and not parent.children:
                    heapq.heappush(heap, (parent.last_used, id(parent), parent))
        return freed

    # -- router summary -------------------------------------------------- #
    def attach_summary(self, summary) -> None:
        """Attach a router prefix summary (``on_insert(sig)`` /
        ``on_evict(sig)``) and replay the current trie into it — one
        walk at attach time; every later refresh is the O(1) per-node
        hook above, never another walk."""
        with self._lock:
            self.summary = summary
            stack = list(self._root.children.values())
            while stack:
                n = stack.pop()
                summary.on_insert(n.sig)
                stack.extend(n.children.values())

    # -- introspection --------------------------------------------------- #
    def hit_rate(self) -> Optional[float]:
        with self._lock:
            return (self.hits / self.lookups) if self.lookups else None

    def stats(self) -> dict:
        with self._lock:
            return {
                "nodes": self.nodes,
                "lookups": self.lookups,
                "hits": self.hits,
                "hit_rate": (self.hits / self.lookups
                             if self.lookups else None),
                "prefill_tokens_saved": self.matched_tokens,
                "inserted_blocks": self.inserted_blocks,
                "evictions": self.evictions,
            }
