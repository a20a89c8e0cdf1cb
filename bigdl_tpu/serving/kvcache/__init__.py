"""Paged KV cache for LM serving: block arena + radix prefix sharing.

- :class:`BlockPool` — one fixed-shape HBM k/v arena of contiguous,
  lane-padded blocks (the layout is stated once, in
  :mod:`~bigdl_tpu.serving.kvcache.blocks`), host-side free list,
  refcounted so block chains are shared copy-free.  What a cached row is,
  the model says: a ``(k, v)`` pair a K/V head, or ONE latent row a
  position (``latent=True``: one arena, no second).
- :class:`RadixCache` — token-prefix trie over block chains with LRU
  eviction of unreferenced tails; admission reuses the longest cached
  prefix and prefills only the suffix.
- :class:`RequestExceedsPool` / :class:`PoolExhausted` — the permanent
  vs transient exhaustion types (reject vs defer).
"""
from bigdl_tpu.serving.kvcache.blocks import (SCRATCH_BLOCK, BlockPool,
                                              PoolExhausted,
                                              RequestExceedsPool)
from bigdl_tpu.serving.kvcache.radix import RadixCache

__all__ = ["BlockPool", "RadixCache", "PoolExhausted",
           "RequestExceedsPool", "SCRATCH_BLOCK"]
