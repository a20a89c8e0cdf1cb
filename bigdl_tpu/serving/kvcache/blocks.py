"""Paged KV memory: one HBM-resident block arena + host-side free list.

The slot engine of PR 5 gave every decode slot a private contiguous
``(cache_len,)`` KV region: admission had to reject any prompt longer
than one region, and identical prompt prefixes were recomputed and
stored once per request.  ``BlockPool`` is the vLLM-style alternative
(PagedAttention, SOSP'23), TPU-native: KV memory is ONE device array of
fixed-size blocks

    k, v : (L, num_blocks, block_len, W),   W = H * D rounded up to 128

and a *sequence* is a host-side list of block ids (its block table).
**A CLASS of blocks a kind of layer.**  The model's softmax layers are
grouped by what they cache and for how long -- (K/V heads, key lanes, value
lanes, window), ``TransformerLM.cache_classes`` -- and the pool holds one
:class:`KVClass` for each: its own ``(k, v)`` arenas ``(L_class, N_class,
block_len, W_k)`` / ``(.., W_v)`` (keys and values may differ in width), its
own free list and refcounts.  A model whose softmax layers are all of one
kind is a pool of ONE class: today's arenas, today's programs, and a chain
entry is a block id.  With several classes a chain entry is a TUPLE of ids,
one a class, all standing for the same ``block_len`` positions: entry ``i``
of a chain holds positions ``i * block_len ..`` in every class, so every
class's table keeps the chain's width and index.  A class WITH A WINDOW lets
go of what lies behind it: as a sequence advances (:meth:`BlockPool.advance`,
after every prefill chunk and before every round) its reference on every
block that lies wholly behind ``pos - window + 1`` is dropped -- the entry's
id of that class becomes the scratch block, which the window's mask already
hides, so no kernel's index arithmetic changes -- and blocks ahead are
allotted as the write position reaches them: a decoding sequence holds at
most ``ceil(window / block_len) + 1`` blocks of such a class whatever its
context.  The radix cache keeps a node's block in EVERY class (its own
reference: a live sequence's release drops the sequence's alone) and evicts
them together.
**What a cached row is, the model says**: a ``(k, v)`` PAIR a K/V head
(softmax attention over cached keys and values: two arenas, a row the
position's H heads of D values side by side), or ONE LATENT ROW (latent
attention, ``BlockPool(latent=True)``: a position's ``[c ; k_r]``, with no
head axis -- H = 1, D the row's lanes, 576 -> 640 -- and NO SECOND ARENA:
the step's values are the row's own first lanes).  ``row_width``,
``block_bytes``, ``wire_shape``, ``arenas`` and ``stats()`` follow from it.
**This is the one place the arena layout is written down.**  A block is
``block_len`` position rows, a row is the position's H heads of D values
side by side, zero-padded to whole 128-lane tiles (1600 -> 1664 at GPT-2
XL; nothing where ``H * D`` already is a multiple of 128), so on the TPU
a block is contiguous and tile-dense and the compiler keeps the block
index major: a scatter of rows and a gather of blocks touch those rows
and blocks and nothing else.  (The previous ``(L, N, H, block_len, D)``
compiled to a layout with N in the LANES, ``{1,4,3,2,0}``, and every
program that touched a block first re-laid the whole arena out; PERF.md,
PR 25.)  Every program reaches the arenas through :func:`write_rows` and
:func:`read_chain` below and indexes them by layer itself — the arenas
ride the layer loop's carry whole, never sliced and re-stacked — so
nothing else knows the layout.  The int8 pool's per-(position, head)
f32 scale arenas go through the same two functions; theirs is ``(L,
num_blocks, C)``, a block's ``block_len * H`` scales (position-major) in
ONE row padded to whole tiles (400 -> 512 at GPT-2 XL): the same
compile-only reading as the data arenas (``(.., block_len, H)`` and
``(.., H, block_len)`` both compile with N in the lanes and a copy of
the arena every layer; H padded to 128 lanes stays in place at five
times the bytes).

The device arrays never change shape — prefill scatters rows into
blocks, the steps gather blocks by an int32 **live list** — so the AOT
executables of the serving engine survive untouched and donation keeps
the arena resident.  Everything dynamic (allocation, refcounts,
sharing) lives on the host in this class, where it costs nothing per
token.

**The live list** is what a step reads by: a flat ``(3, n)`` int32
operand naming, entry by entry, WHICH block, WHOSE (the owning slot) and
WHERE in that slot's chain (so entry ``i`` holds its owner's positions
``where * block_len ..``).  A decode round lists only the blocks its
active slots hold up to their write positions (:func:`live_list`, built
on the host) and pads the list to the entries of whole tables; the step
walks it a chunk at a time (:func:`list_chunk`) and stops after the
last chunk that holds a listed block -- one executable for every length
-- so the bytes a round gathers follow the SUM of
the live blocks, not slots x the table width.  Entries are sorted by
owner; a padded entry comes last, names the scratch block and is owned
by NOBODY (owner ``S``, one past the slots), so no slot's softmax sees
it.  The steps that still carry whole ``(S, M)`` tables (verify, tree
verify) read through the same function with the degenerate list of
every table entry (:func:`table_list`).

A step attends a list without an f32 copy or a re-layout of what it
gathered (``generate._paged_attention``).  Where a slot has ONE query
vector for a K/V head (plain decode, heads not grouped) the listed
chains are read as one chain (:func:`read_chain`) and every slot's
query meets every listed position, a head at a time, the mask keeping
each slot its own.  Where it has several (grouped heads, a verify
step's candidate rows) the blocks' rows as they lie, ``(n * block_len,
W)`` (:func:`read_rows`), meet the queries in two grouped matmuls whose
groups are the owners' runs of rows: a slot's queries become COLUMNS
that are zero outside their K/V head's lanes (:func:`head_columns`), so
``rows @ columns`` is every position's score under every head; the
weighted V rows of a slot come back as ``(columns, W)`` and
:func:`head_lanes` keeps each column's own head.  Only these functions
know where a head lies in a row.  A LATENT pool's walk (every head's
absorbed query against the one row a position, the grouped case with one
K/V head) is the CPU path and the oracle: on a TPU the decode step reads
the listed blocks where they lie, with no chunk gathered
(``ops.latent_attention``, PERF.md, PR 36).

Block 0 is reserved as a **scratch** block: padded table entries and
padded scatter targets point at it, so fixed-shape gathers/scatters
never need a validity operand — garbage lands in (or comes from)
scratch and is always masked by the position mask.  It is never
allocated and never freed.

Refcounts make chains shareable copy-free: a block referenced by two
live sequences (or a sequence and the radix cache) is freed only when
the last holder releases it.  ``alloc`` hands out blocks at refcount 1;
``retain``/``release`` move them between holders.

Exhaustion is two distinct conditions with two distinct types:

- :class:`RequestExceedsPool` (a ``ValueError``): the request could
  NEVER fit — its total block need exceeds the whole pool.  Raised at
  admission, counted in ``serving/rejected_total``.
- :class:`PoolExhausted` (a ``RuntimeError``): the pool is full *right
  now*.  Transient by construction — blocks free as streams finish —
  so the engine defers the request instead of failing it.

Migration (disaggregated prefill/decode serving): a finished prefill's
block chain moves between pools as a **block-major wire payload**
``(n, L, H, block_len, D)`` (the host tier's and the peers' format,
independent of the arena layout: the conversion runs on the blocks that
move) — ``export_chain`` gathers it to the host in bounded slices,
``adopt_chain`` allocates destination blocks
all-or-nothing and scatters the payload back in over
:func:`~bigdl_tpu.utils.transfer.chunked_device_put` (bounded
32 MB slices: a chain near ``cache_len`` at production geometry is
hundreds of MB).  Block-major layout is deliberate: the wire's leading dim
is the one both the d2h slicer and ``chunked_device_put`` chunk along,
so no single slice ever exceeds the ceiling regardless of L.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

SCRATCH_BLOCK = 0
LANES = 128     # the TPU's minor tile: arena rows are whole multiples of it


def _whole_tiles(n: int) -> int:
    return -(-n // LANES) * LANES


def row_width(n_heads: int, head_dim: int) -> int:
    """Lanes of one position row: ``H * D`` rounded up to whole tiles."""
    return _whole_tiles(n_heads * head_dim)


def pack_rows(rows, width=None, tail: int = 2):
    """Position rows in the arena's row layout: the last ``tail`` axes
    (``(H, D)``) flattened side by side and zero-padded to ``width``
    lanes (:func:`row_width` of them by default)."""
    import jax.numpy as jnp

    flat = rows.reshape(rows.shape[:rows.ndim - tail] + (-1,))
    pad = (width or _whole_tiles(flat.shape[-1])) - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return flat


def write_rows(arena, layer, blk, off, rows):
    """Scatter position rows into an arena, in place under donation:
    ``rows[i]`` — ``(H, D)`` for a data arena ``(L, N, B, W)``, ``(H,)``
    for a scale arena ``(L, N, C)`` — lands at position ``off[i]`` of
    block ``blk[i]`` of ``layer``; ``blk`` and ``off`` share any index
    shape.  ``off=None`` writes whole blocks (``rows[i]`` then leads with
    the ``block_len`` axis), and ``layer`` may be ``slice(None)`` with
    ``rows`` leading with L."""
    import jax.numpy as jnp
    from jax import lax

    every = isinstance(layer, slice)
    rows = rows.astype(arena.dtype)
    if off is None or arena.ndim == 4:
        # index axes: [L] + blk's (+ block_len, a data arena's own axis);
        # what follows them is one row of the arena's last axis
        lead = every + jnp.ndim(blk) + (off is None and arena.ndim == 4)
        flat = pack_rows(rows, arena.shape[-1], rows.ndim - lead)
        if off is None:
            return arena.at[layer, blk].set(flat)
        return arena.at[layer, blk, off].set(flat)
    # a scale arena's row is a whole block: position ``off`` is the
    # window of H lanes at ``off * H`` of it
    h = rows.shape[-1]
    layers = (jnp.arange(arena.shape[0]).reshape((-1,) + (1,) * jnp.ndim(blk))
              if every else layer)
    idx = jnp.stack(jnp.broadcast_arrays(layers, blk, off * h), axis=-1)
    return lax.scatter(
        arena, idx, rows,
        lax.ScatterDimensionNumbers(
            update_window_dims=(rows.ndim - 1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)))


def read_chain(arena, layer, tables, block):
    """Gather block chains out of an arena: ``tables`` (..., M) block ids
    -> ``(..., M * B, H, D)`` from a data arena ``(L, N, B, W)``,
    ``(..., M * B, H)`` from a scale arena ``(L, N, C)``; ``block`` is
    what one block holds, ``(B, H, D)`` or ``(B, H)``.  The gathered axis
    IS the position, ``p -> (p // B, p % B)``.  ``layer=slice(None)``
    keeps a leading L."""
    import numpy as np

    g = arena[layer, tables]                    # (..., M, B, W) | (..., M, C)
    block = tuple(block)
    if arena.ndim == 3:
        g = g[..., :int(np.prod(block))]
        return g.reshape(g.shape[:-2] + (g.shape[-2] * block[0],) + block[1:])
    # merge (M, B) into the position axis BEFORE the padding is cut: in
    # this order the TPU compiler keeps the gathered chain whole and puts
    # the positions in the lanes for the f32 score math; cut first, it
    # materialises the cut and an f32 (.., H, D) copy with D in the lanes
    # (the same decode round read 103 ms, not 33: PERF.md, PR 25)
    g = g.reshape(g.shape[:-3] + (g.shape[-3] * g.shape[-2], g.shape[-1]))
    return g[..., :int(np.prod(block[1:]))].reshape(g.shape[:-1] + block[1:])


def list_chunk(slots: int, grouped: bool = False, latent: bool = False) -> int:
    """Blocks of a live list a step attends at a time: four a slot, or
    sixteen where a chunk costs two ``grouped`` matmuls (each a custom
    call of some 0.1 ms whatever it multiplies: PERF.md, PR 29), or forty
    for a ``latent`` walk (one row a position, every head's query against
    it).  Since PR 36 the latent walk is the CPU path and the oracle of the
    kernel that serves on a TPU (``ops.latent_attention``, which gathers no
    chunk); its forty are the best of a sweep of the walk alone on the chip, at
    32 slots and 51,000 live blocks (PERF.md, PR 35: 16 / 40 / 80 / 160 /
    320 blocks a slot walk in 20.5 / 14.8 / 15.7 / 22.7 / 22.0 ms); inside
    the whole decode step it gave a twentieth, not a third (160 -> 40
    blocks a slot: 970-980 -> 1,011-1,014 tokens/s; why the loop inside the
    step is not the loop alone is open there).  A step loops over as many
    chunks as hold a listed block, so what a round gathers follows its live
    blocks to within a chunk, in ONE executable for every length a list can
    have."""
    if latent:
        return 40 * int(slots)
    return (16 if grouped else 4) * int(slots)


def live_list(chains, length: int, slots: int):
    """The ``(3, length)`` int32 live list of one round: ``chains`` is
    ``[(slot, block ids[, first])]`` by ascending slot, each slot's blocks in
    chain order and only the ones the round reads, from chain index
    ``first`` on (0 where not given; a windowed class lists its window's
    blocks alone); padded with scratch entries that nobody owns (module
    docstring)."""
    import numpy as np

    out = np.zeros((3, length), np.int32)
    out[1] = slots
    at = 0
    for slot, blocks, *first in chains:
        n = len(blocks)
        out[0, at:at + n] = blocks
        out[1, at:at + n] = slot
        out[2, at:at + n] = np.arange(n) + (first[0] if first else 0)
        at += n
    return out


def window_blocks(window: int, block_len: int) -> int:
    """Blocks a window's positions can touch: what a decoding sequence
    holds of a windowed class at most (``ceil(window / block_len) + 1``)."""
    return -(-int(window) // int(block_len)) + 1


def class_entries(slots: int, table_width: int, window, block_len: int) -> int:
    """Entries of a class's share of a round's live list: whole tables, or,
    under a ``window``, the blocks a window can touch a slot."""
    per = table_width if window is None else min(
        table_width, window_blocks(window, block_len))
    return int(slots) * int(per)


def split_live(live, slots: int, table_width, windows, block_len: int):
    """A round's live list by class: the classes' lists lie side by side
    along the entries, each of :func:`class_entries` of them.  One class:
    the list itself."""
    if len(windows) == 1:
        return (live,)
    out, at = [], 0
    for w in windows:
        n = class_entries(slots, table_width, w, block_len)
        out.append(live[:, at:at + n])
        at += n
    return tuple(out)


def table_list(tables):
    """Whole tables ``(S, M)`` as the degenerate live list ``(3, S * M)``:
    every entry of every slot, scratch padding and all (the position
    mask hides what a slot does not hold, as it always did)."""
    import jax.numpy as jnp

    s, m = tables.shape
    return jnp.stack([tables.reshape(-1),
                      jnp.repeat(jnp.arange(s, dtype=tables.dtype), m),
                      jnp.tile(jnp.arange(m, dtype=tables.dtype), s)])


def read_rows(arena, layer, ids):
    """The listed blocks' position rows as they lie in a data arena,
    block index major: ``ids`` (n,) -> ``(n * block_len, W)``."""
    g = arena[layer, ids]
    return g.reshape((g.shape[0] * g.shape[1], g.shape[2]))


def head_columns(q, width: int):
    """Queries as columns over a position row's lanes: ``q`` (S, H_kv, C,
    D), the C query vectors that read K/V head ``k`` -> ``(S, width,
    H_kv * C)``, column ``(k, c)`` holding ``q[s, k, c]`` at head k's D
    lanes and zeros elsewhere (the lane padding too)."""
    import jax.numpy as jnp

    s, k, c, d = q.shape
    own = jnp.eye(k, dtype=q.dtype)[None, :, None, :, None]
    cols = q.transpose(0, 1, 3, 2)[:, :, :, None, :] * own   # (S, k, D, k', C)
    return jnp.pad(cols.reshape(s, k * d, k * c),
                   ((0, 0), (0, width - k * d), (0, 0)))


def head_lanes(x, n_kv: int, head_dim: int):
    """The inverse read: ``x`` (S, H_kv * C, W), one row of lanes a
    column -> ``(S, H_kv, C, D)``, column ``(k, c)``'s head-k lanes."""
    import jax.numpy as jnp

    s, kc, _ = x.shape
    x = x[..., :n_kv * head_dim].reshape(s, n_kv, kc // n_kv, n_kv, head_dim)
    own = jnp.eye(n_kv, dtype=x.dtype)[None, :, None, :, None]
    return jnp.sum(x * own, axis=3)


class PoolExhausted(RuntimeError):
    """Transient: no free blocks at this instant; retry after streams
    complete or the radix cache evicts unreferenced tails."""


class RequestExceedsPool(ValueError):
    """Permanent: the request's total KV need (prompt + generation
    budget, in blocks) exceeds the whole pool — it can never be
    admitted.  Counted in ``serving/rejected_total``."""


class KVClass:
    """One class of blocks (module docstring): the arenas of the layers that
    cache the same row for the same lifetime, their free list and their
    refcounts.  ``k`` (L, N, block_len, W_k) and ``v`` (.., W_v), ``ks`` /
    ``vs`` the int8 scales' (None otherwise); a latent class has ``v`` None."""

    def __init__(self, *, n_layers: int, n_heads: int, head_dim: int,
                 v_dim: Optional[int], window: Optional[int], block_len: int,
                 num_blocks: int, dtype, kv_quant, latent: bool):
        import jax.numpy as jnp

        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is scratch), got "
                f"{num_blocks}")
        self.n_layers, self.n_heads, self.head_dim = (
            int(n_layers), int(n_heads), int(head_dim))
        self.v_dim = int(v_dim or head_dim)
        self.window = None if window is None else int(window)
        self.num_blocks = int(num_blocks)
        self.block_len = int(block_len)
        #: the arenas' shapes (module docstring): the lane padding follows
        #: from the geometry the class is built with, nothing selects it
        lead = (self.n_layers, self.num_blocks, self.block_len)
        self.shape = lead + (row_width(self.n_heads, self.head_dim),)
        self.v_shape = lead + (row_width(self.n_heads, self.v_dim),)
        self.scale_shape = lead[:2] + (
            _whole_tiles(self.block_len * self.n_heads),)
        if kv_quant == "int8":
            self.k = jnp.zeros(self.shape, jnp.int8)
            self.v = jnp.zeros(self.v_shape, jnp.int8)
            # per-(position, head) scales, block-major like the arenas
            self.ks = jnp.zeros(self.scale_shape, jnp.float32)
            self.vs = jnp.zeros(self.scale_shape, jnp.float32)
        else:
            dt = dtype if dtype is not None else jnp.float32
            self.k = jnp.zeros(self.shape, dt)
            self.v = None if latent else jnp.zeros(self.v_shape, dt)
            self.ks = self.vs = None
        # pop() from the tail hands out ascending ids first
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._ref = [0] * self.num_blocks

    @property
    def arenas(self) -> tuple:
        if self.v is None:
            return (self.k,)
        return ((self.k, self.v) if self.ks is None
                else (self.k, self.v, self.ks, self.vs))

    @arenas.setter
    def arenas(self, new) -> None:
        if self.v is None:
            (self.k,) = new
        elif self.ks is None:
            self.k, self.v = new
        else:
            self.k, self.v, self.ks, self.vs = new

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def row_lanes(self) -> int:
        """A position's lanes in one layer, unpadded: keys and values."""
        return self.n_heads * (self.head_dim
                               + (0 if self.v is None else self.v_dim))

    @property
    def kv_arena_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.arenas[:2])

    def stats(self) -> dict:
        free = len(self._free)
        return {"layers": self.n_layers, "kv_heads": self.n_heads,
                "row_lanes": self.row_lanes, "window": self.window,
                "num_blocks": self.num_blocks, "free_blocks": free,
                "used_blocks": self.capacity - free,
                "bytes": self.kv_arena_bytes}


class BlockPool:
    """Refcounted free-list allocator over the paged k/v arenas of one or
    several classes of blocks, or over one arena of latent rows.

    Args:
        n_layers / n_heads / head_dim: model geometry (L, H, D) of a pool of
            ONE class: the layers that cache a row, and what a row is -- H K/V
            heads of D, or, with ``latent``, one head of the latent row's
            lanes.
        classes: the pool's classes where the model's softmax layers are of
            several kinds (module docstring): a dict a class, ``n_layers``,
            ``n_heads``, ``head_dim`` and optionally ``v_dim`` (values of
            their own width), ``window`` and ``num_blocks`` (the pool's
            where not given).  In place of the three above.
        latent: the cached row is ONE latent row a position (module
            docstring): one arena ``self.k``, ``self.v`` is None, and what
            carries ``(k, v)`` pairs -- ``kv_quant`` (a scale a head),
            ``export_chain`` / ``adopt_chain`` (the wire format) -- is
            refused.
        block_len: tokens per block (the page size).
        num_blocks: total blocks INCLUDING the reserved scratch block 0;
            usable capacity is ``num_blocks - 1``.
        dtype: cache dtype (defaults to f32; the engine passes the
            params' embed dtype).
        kv_quant: ``None`` (full-precision arenas) or ``"int8"`` —
            int8 block arenas plus per-(position, head) f32 scale
            arenas ``self.ks`` / ``self.vs`` (module docstring).  The
            paged gather dequantizes in-flight (see
            ``generate._decode_step_paged``); storage drops ~4x minus
            the 1/D scale overhead.  Lossy: streams are NOT bit-exact
            vs a full-precision pool.  Chain export/adopt bundles the
            scale arrays atomically with the data (the host KV tier
            and hibernation ride this); disaggregated serving still
            keeps full-precision pools.  Scale arenas a class.

    The jnp arenas are held a class (``self.classes[c].k`` ..); ``self.k`` /
    ``self.v`` (plus ``self.ks`` / ``self.vs`` when quantized) are the
    PRIMARY class's -- the first without a window, the one ``capacity``,
    ``free_count`` and ``shape`` speak of -- and ``self.arenas`` every
    class's side by side; callers that run donated executables over them
    reassign the attribute with the donated outputs (same contract as the
    slot engine's resident caches).
    """

    def __init__(self, *, n_layers: Optional[int] = None,
                 n_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 block_len: int, num_blocks: Optional[int] = None, dtype=None,
                 kv_quant: Optional[str] = None, latent: bool = False,
                 classes: Optional[Sequence[dict]] = None):
        self.latent = bool(latent)
        if classes is None:
            classes = [dict(n_layers=n_layers, n_heads=n_heads,
                            head_dim=head_dim)]
        if self.latent and (kv_quant is not None or len(classes) > 1
                            or classes[0]["n_heads"] != 1):
            raise ValueError("a latent pool holds one full-precision row a "
                             "position: n_heads 1, no kv_quant")
        if block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {block_len}")
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {kv_quant!r}")
        self.block_len = int(block_len)
        self.kv_quant = kv_quant
        self.classes: List[KVClass] = [
            KVClass(n_layers=c["n_layers"], n_heads=c["n_heads"],
                    head_dim=c["head_dim"], v_dim=c.get("v_dim"),
                    window=c.get("window"), block_len=self.block_len,
                    num_blocks=(c.get("num_blocks") or num_blocks or 0),
                    dtype=dtype, kv_quant=kv_quant, latent=self.latent)
            for c in classes]
        #: a chain entry is a tuple of ids, one a class (else: a block id)
        self._multi = len(self.classes) > 1
        #: the classes with a window, which let go of what lies behind it
        self.windowed = tuple(i for i, c in enumerate(self.classes)
                              if c.window is not None)
        full = [i for i, c in enumerate(self.classes) if c.window is None]
        self._primary = self.classes[full[0] if full else 0]
        self.dtype = self._primary.k.dtype
        self._lock = threading.Lock()
        self._adopt_jits: dict = {}  # padded wire width -> donated scatter
        #: blocks of the windowed classes let go behind a window, in all
        self.window_released = 0

    # -- the primary class's geometry, as a pool of one class states it ---- #
    num_blocks = property(lambda self: self._primary.num_blocks)
    n_layers = property(lambda self: self._primary.n_layers)
    n_heads = property(lambda self: self._primary.n_heads)
    head_dim = property(lambda self: self._primary.head_dim)
    shape = property(lambda self: self._primary.shape)
    scale_shape = property(lambda self: self._primary.scale_shape)

    def _arena(name):      # the primary class's arena, read and assigned
        return property(lambda self: getattr(self._primary, name),
                        lambda self, new: setattr(self._primary, name, new))

    k, v, ks, vs = _arena("k"), _arena("v"), _arena("ks"), _arena("vs")
    del _arena

    @property
    def wire_shape(self) -> tuple:
        """One block on the wire / in the host tier: (L, H, block_len, D)."""
        c = self._primary
        return (c.n_layers, c.n_heads, self.block_len, c.head_dim)

    @property
    def arenas(self) -> tuple:
        """``(k, v)``, quantized ``(k, v, ks, vs)``, a latent pool's one
        ``(rows,)``, or several classes' ``(k, v)`` side by side in class
        order: what the donated executables take and hand back (assign their
        outputs here)."""
        return tuple(a for c in self.classes for a in c.arenas)

    @arenas.setter
    def arenas(self, new) -> None:
        new, at = tuple(new), 0
        for c in self.classes:
            n = len(c.arenas)
            c.arenas = new[at:at + n]
            at += n

    def _ids(self, entry) -> tuple:
        """A chain entry's block id a class."""
        return tuple(entry) if self._multi else (entry,)

    def entry(self, ids):
        """The chain entry of one id a class."""
        return tuple(int(b) for b in ids) if self._multi else int(ids[0])

    def table(self, chain, width: int):
        """A chain as scratch-padded tables, one row a class: (C, width)
        int32."""
        import numpy as np
        out = np.zeros((len(self.classes), int(width)), np.int32)
        if len(chain):
            out[:, :len(chain)] = np.asarray(chain, np.int32).reshape(
                len(chain), -1).T
        return out

    def whole(self, entry) -> bool:
        """Whether the entry still holds a block in every class."""
        return all(b != SCRATCH_BLOCK for b in self._ids(entry))

    # -- capacity -------------------------------------------------------- #
    @property
    def capacity(self) -> int:
        """Usable blocks of the primary class (scratch excluded)."""
        return self._primary.capacity

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._primary._free)

    def free_in(self, c: int) -> int:
        """Free blocks of class ``c``."""
        with self._lock:
            return len(self.classes[c]._free)

    @property
    def used_count(self) -> int:
        return self.capacity - self.free_count

    @property
    def data_arenas(self) -> int:
        """How many arenas hold rows: k and v, or the one of latent rows."""
        return 1 if self.latent else 2

    @property
    def row_bytes(self) -> int:
        """One position's row in one layer as the arenas hold it, lane
        padding included (both of a pair)."""
        c = self._primary
        return (c.shape[3] + (0 if self.latent else c.v_shape[3])
                ) * self.dtype.itemsize

    @property
    def kv_arena_bytes(self) -> int:
        """HBM footprint of the data arenas alone (k + v of every class, or
        the latent rows')."""
        return sum(c.kv_arena_bytes for c in self.classes)

    @property
    def scale_arena_bytes(self) -> int:
        """HBM footprint of the int8 per-(position, head) scale arenas
        (0 for a full-precision pool) — ledgered separately from the
        data arenas so quantized capacity planning sees the overhead."""
        return sum(2 * c.ks.size * c.ks.dtype.itemsize
                   for c in self.classes if c.ks is not None)

    def _one_class(self, what: str) -> None:
        if self._multi or self.windowed:
            raise NotImplementedError(
                f"{what}: a chain of several classes, or of one that lets go "
                f"of what lies behind its window, has no wire format "
                f"(ROADMAP M3)")

    @property
    def arena_bytes(self) -> int:
        """HBM footprint of the k + v arenas (+ scale arenas when
        quantized)."""
        return self.kv_arena_bytes + self.scale_arena_bytes

    def utilization(self) -> float:
        return self.used_count / self.capacity if self.capacity else 0.0

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache positions."""
        return -(-int(n_tokens) // self.block_len)

    def rows_at(self, chain, positions) -> tuple:
        """The cached rows of one ``chain`` (block ids in chain order) at
        ``positions`` as the data arenas hold them, the lane padding cut: one
        host array an arena -- k and v, or the latent rows -- of (layers,
        positions, H * D).  For a check that compares what was cached with a
        reference's rows; an int8 pool's come without their scales."""
        import numpy as np
        chain = np.asarray(chain, np.int32)
        positions = np.asarray(positions, np.int64)
        self._one_class("rows_at")
        blk, off = chain[positions // self.block_len], positions % self.block_len
        c = self._primary
        return tuple(np.asarray(a[:, blk, off, :c.n_heads * d])
                     for a, d in zip(c.arenas[:self.data_arenas],
                                     (c.head_dim, c.v_dim)))

    # -- alloc / refcount ------------------------------------------------ #
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` chain entries at refcount 1; all-or-nothing.  A class
        with a window allots NOTHING here (its ids are the scratch block's):
        its blocks come as the sequence reaches them (:meth:`advance`)."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            for i, c in enumerate(self.classes):
                if c.window is None and n > len(c._free):
                    raise PoolExhausted(
                        f"need {n} blocks, {len(c._free)} free "
                        f"(capacity {c.capacity}"
                        + (f", class {i})" if self._multi else ")"))
            ids = []
            for c in self.classes:
                if c.window is not None:
                    ids.append([SCRATCH_BLOCK] * n)
                    continue
                ids.append([c._free.pop() for _ in range(n)])
                for b in ids[-1]:
                    c._ref[b] = 1
        return [self.entry(e) for e in zip(*ids)]

    def advance(self, chain: list, marks: list, pos: int, upto: int) -> tuple:
        """Move a sequence's WINDOWED classes on, in place: a query at
        ``pos`` or later is all that is still to run and positions below
        ``upto`` are about to be written.  Of each such class the sequence's
        reference on every block that lies wholly behind ``pos - window + 1``
        is dropped (the entry's id becomes the scratch block's; ``marks[c]``,
        the sequence's own note, is the first entry not yet let go) and the
        entries from there up to ``upto``'s block that hold none are
        allotted.  -> (blocks released, blocks allotted); raises
        :class:`PoolExhausted`, naming the class, with nothing allotted."""
        released = allotted = 0
        if not self.windowed:
            return released, allotted
        B = self.block_len
        hi = min(-(-int(upto) // B), len(chain))
        with self._lock:
            plan = []
            for c in self.windowed:
                cls = self.classes[c]
                keep = max(0, int(pos) - cls.window + 1) // B
                want = [i for i in range(max(keep, marks[c]), hi)
                        if self._ids(chain[i])[c] == SCRATCH_BLOCK]
                freed = sum(
                    1 for i in range(marks[c], min(keep, len(chain)))
                    if self._ids(chain[i])[c] != SCRATCH_BLOCK
                    and cls._ref[self._ids(chain[i])[c]] == 1)
                if len(want) > len(cls._free) + freed:
                    raise PoolExhausted(
                        f"need {len(want)} blocks of class {c} (window "
                        f"{cls.window}), {len(cls._free)} free (capacity "
                        f"{cls.capacity})")
                plan.append((c, cls, keep, want))
            for c, cls, keep, want in plan:
                for i in range(marks[c], min(keep, len(chain))):
                    ids = list(self._ids(chain[i]))
                    if ids[c] != SCRATCH_BLOCK:
                        self._drop(cls, ids[c])
                        ids[c] = SCRATCH_BLOCK
                        chain[i] = self.entry(ids)
                        released += 1
                marks[c] = max(marks[c], min(keep, len(chain)))
                for i in want:
                    ids = list(self._ids(chain[i]))
                    ids[c] = cls._free.pop()
                    cls._ref[ids[c]] = 1
                    chain[i] = self.entry(ids)
                    allotted += 1
            self.window_released += released
        return released, allotted

    def held(self, chain, c: int) -> int:
        """Blocks of class ``c`` the chain's entries hold."""
        return sum(self._ids(e)[c] != SCRATCH_BLOCK for e in chain)

    @staticmethod
    def _drop(cls: KVClass, b: int) -> None:
        if cls._ref[b] <= 0:
            raise ValueError(f"release of free block {b}")
        cls._ref[b] -= 1
        if cls._ref[b] == 0:
            cls._free.append(b)

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one reference to each (already-live) entry, in every class
        (a windowed class's entry that was let go holds nothing)."""
        with self._lock:
            for e in blocks:
                for cls, b in zip(self.classes, self._ids(e)):
                    if b == SCRATCH_BLOCK and cls.window is not None:
                        continue
                    if cls._ref[b] <= 0:
                        raise ValueError(f"retain of free block {b}")
                    cls._ref[b] += 1

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference; a block at zero returns to its class's free
        list."""
        with self._lock:
            for e in blocks:
                for cls, b in zip(self.classes, self._ids(e)):
                    if b == SCRATCH_BLOCK and cls.window is not None:
                        continue
                    self._drop(cls, b)

    def refcount(self, block) -> int:
        """An entry's references: the most any class's block of it has."""
        with self._lock:
            return max(cls._ref[b]
                       for cls, b in zip(self.classes, self._ids(block)))

    # -- migration (disaggregated prefill/decode) ------------------------ #
    @property
    def block_bytes(self) -> int:
        """Bytes of one block's k (== v) rows across all layers ON THE
        WIRE (no lane padding) — the unit both chunkers slice on."""
        import numpy as np
        return int(np.prod(self.wire_shape)) * self.dtype.itemsize

    @property
    def scale_block_bytes(self) -> int:
        """Bytes of one block's k (== v) per-(position, head) scale
        rows; 0 for full-precision pools."""
        if self.ks is None:
            return 0
        return (self.n_layers * self.n_heads * self.block_len
                * self.ks.dtype.itemsize)

    @property
    def wire_block_bytes(self) -> int:
        """Per-block wire bytes of one k (== v) leg INCLUDING its scale
        rows — the unit the chunkers budget on, so a quantized block's
        scales count against the same 32 MB transfer ceiling as its
        data."""
        return self.block_bytes + self.scale_block_bytes

    def export_chain(self, blocks: Sequence[int], *,
                     chunk_bytes: Optional[int] = None) -> dict:
        """Gather ``blocks``' k/v rows to the host as a block-major
        wire payload ``{"k", "v": (n, L, H, block_len, D) np, "blocks": n}``.

        A quantized pool (``kv_quant="int8"``) exports its
        per-(position, head) scales ATOMICALLY with the data — the
        payload gains ``"ks"`` / ``"vs"`` arrays shaped ``(n, L, H,
        block_len)`` f32, and scale bytes count against the chunk
        budget — so an adopted block is bit-identical to the exported
        one, never data without its dequantization state.

        Device->host moves in slices of at most ``chunk_bytes`` (the
        shared 32 MB transfer ceiling by default) along the block dim,
        one in flight at a time — the same discipline as
        ``chunked_device_put``, mirrored for the download leg.  The
        caller keeps its references; exporting never touches refcounts.
        """
        import jax.numpy as jnp
        import numpy as np

        from bigdl_tpu.utils.transfer import DEFAULT_CHUNK_BYTES
        self._pairs_only("export_chain")
        cb = int(chunk_bytes) if chunk_bytes else DEFAULT_CHUNK_BYTES
        n = len(blocks)
        L, H, B, D = self.wire_shape
        quant = self.kv_quant is not None
        host_k = np.empty((n, L, H, B, D), self.dtype)
        host_v = np.empty((n, L, H, B, D), self.dtype)
        host_ks = np.empty((n, L, H, B), np.float32) if quant else None
        host_vs = np.empty((n, L, H, B), np.float32) if quant else None
        if n:
            idx = jnp.asarray(list(blocks), jnp.int32)

            def wire(arena, tail):
                # device-side gather of the n blocks, then (L, n*B, H, ..)
                # -> the block-major wire layout (n, L, H, B, ..)
                g = read_chain(arena, slice(None), idx, (B,) + tail)
                g = g.reshape((L, n, B) + tail)
                return jnp.moveaxis(jnp.moveaxis(g, 3, 2), 1, 0)

            kc, vc = wire(self.k, (H, D)), wire(self.v, (H, D))
            if quant:
                ksc, vsc = wire(self.ks, (H,)), wire(self.vs, (H,))
            rows = max(1, cb // max(1, self.wire_block_bytes))
            for i in range(0, n, rows):
                host_k[i:i + rows] = np.asarray(kc[i:i + rows])
                host_v[i:i + rows] = np.asarray(vc[i:i + rows])
                if quant:
                    host_ks[i:i + rows] = np.asarray(ksc[i:i + rows])
                    host_vs[i:i + rows] = np.asarray(vsc[i:i + rows])
        out = {"k": host_k, "v": host_v, "blocks": n}
        if quant:
            out["ks"] = host_ks
            out["vs"] = host_vs
        return out

    def _pairs_only(self, what: str) -> None:
        self._one_class(what)
        if self.latent:
            raise NotImplementedError(
                f"{what}: the wire format is a (k, v) pair, and this pool "
                f"holds one latent row a position (ROADMAP M4)")

    def _adopt_scatter(self, width: int):
        """Donated scatter of a ``width``-block wire payload into the
        arenas; one executable per padded wire width (powers of two),
        padded entries target the scratch block with zero rows.  A
        quantized pool's scatter writes data and scale arenas in ONE
        executable — a block can never land without its scales."""
        exe = self._adopt_jits.get(width)
        if exe is None:
            import jax
            import jax.numpy as jnp

            def put(arena, wire, ids):
                # wire (w, L, H, B, ..) -> whole blocks (L, w, B, H, ..)
                rows = jnp.moveaxis(jnp.moveaxis(wire, 0, 1), 2, 3)
                return write_rows(arena, slice(None), ids, None, rows)

            n = len(self.arenas)

            def _scatter(*ops):     # the arenas, their wire legs, the ids
                return tuple(put(a, w, ops[-1])
                             for a, w in zip(ops[:n], ops[n:2 * n]))

            exe = jax.jit(_scatter, donate_argnums=tuple(range(n)))
            self._adopt_jits[width] = exe
        return exe

    def warmup_adopt(self, widths: Sequence[int]) -> int:
        """Pre-compile AND prime the donated adopt scatters for the
        given padded wire widths, so the first real migration doesn't
        pay a mid-traffic compile.  Runs each executable once with a
        zero payload aimed entirely at the scratch block — garbage
        there is always masked — which also keeps the arenas resident
        through the donation."""
        import jax.numpy as jnp
        import numpy as np
        self._pairs_only("warmup_adopt")
        n = 0
        for w in widths:
            w = int(w)
            if w < 1:
                continue
            kw = jnp.zeros((w,) + self.wire_shape, self.dtype)
            if getattr(self.k, "sharding", None) is not None:
                import jax
                kw = jax.device_put(kw, self.k.sharding)
            idx = np.full((w,), SCRATCH_BLOCK, np.int32)
            if self.kv_quant is not None:
                sw = jnp.zeros((w,) + self.wire_shape[:3], jnp.float32)
                if getattr(self.ks, "sharding", None) is not None:
                    import jax
                    sw = jax.device_put(sw, self.ks.sharding)
                wire = (kw, kw, sw, sw)
            else:
                wire = (kw, kw)
            self.arenas = self._adopt_scatter(w)(*self.arenas, *wire, idx)
            n += 1
        return n

    def adopt_chain(self, k_wire, v_wire, ks_wire=None, vs_wire=None, *,
                    extra_blocks: int = 0, device=None,
                    chunk_bytes: Optional[int] = None) -> List[int]:
        """Adopt an exported chain into THIS pool: allocate
        ``n_wire + extra_blocks`` blocks (all-or-nothing — a partial
        adoption would strand a half-migrated sequence), stage the wire
        payload over ``chunked_device_put`` and scatter it into the
        first ``n_wire`` of them.  Returns the new block ids, each at
        refcount 1 (the adopting sequence's references).

        A quantized pool (``kv_quant="int8"``) REQUIRES the matching
        scale arrays ``ks_wire`` / ``vs_wire`` (shape ``(n, L, H,
        block_len)``) from :meth:`export_chain` — data and scales land
        through one donated scatter, and the data legs' chunk budget is
        shrunk by the scale share so data + scales together respect the
        32 MB transfer ceiling.  The adopted block is bit-identical to
        the exported one.

        ``extra_blocks`` reserves the generation tail in the same
        atomic allocation.  ``device`` is the arena's committed
        sharding/device (a placement slice's replicated sharding).  On
        transfer failure every allocated block is released before the
        error propagates — the pool is left exactly as found.
        :class:`PoolExhausted` propagates untouched so callers keep the
        typed defer path.
        """
        import numpy as np

        from bigdl_tpu.utils.transfer import (DEFAULT_CHUNK_BYTES,
                                              chunked_device_put)
        self._pairs_only("adopt_chain")
        k_wire = np.asarray(k_wire)
        v_wire = np.asarray(v_wire)
        n = int(k_wire.shape[0]) if k_wire.ndim else 0
        if v_wire.shape != k_wire.shape:
            raise ValueError(
                f"k/v wire shapes differ: {k_wire.shape} vs {v_wire.shape}")
        quant = self.kv_quant is not None
        if quant and n and (ks_wire is None or vs_wire is None):
            raise ValueError(
                "adopting into a quantized pool (kv_quant='int8') "
                "requires the ks/vs scale arrays exported with the "
                "chain — data without scales cannot dequantize")
        if not quant and (ks_wire is not None or vs_wire is not None):
            raise ValueError(
                "scale arrays supplied for a full-precision pool")
        if quant and n:
            ks_wire = np.asarray(ks_wire, np.float32)
            vs_wire = np.asarray(vs_wire, np.float32)
            want = (n,) + self.wire_shape[:3]
            if ks_wire.shape != want or vs_wire.shape != want:
                raise ValueError(
                    f"scale wire shapes {ks_wire.shape} / "
                    f"{vs_wire.shape} do not match blocks {want}")
        ids = self.alloc(n + max(0, int(extra_blocks)))
        if n == 0:
            return ids
        cb = int(chunk_bytes) if chunk_bytes else DEFAULT_CHUNK_BYTES
        # scale bytes ride the same budget: a data slice plus its scale
        # slice together stay under ``cb``
        data_cb = max(1, cb * self.block_bytes
                      // max(1, self.wire_block_bytes))
        try:
            kw = chunked_device_put(k_wire, self.dtype,
                                    chunk_bytes=data_cb, device=device)
            vw = chunked_device_put(v_wire, self.dtype,
                                    chunk_bytes=data_cb, device=device)
            if quant:
                scale_cb = max(1, cb - data_cb)
                ksw = chunked_device_put(ks_wire, np.float32,
                                         chunk_bytes=scale_cb,
                                         device=device)
                vsw = chunked_device_put(vs_wire, np.float32,
                                         chunk_bytes=scale_cb,
                                         device=device)
            # pad the wire to a power-of-two width so the donated
            # scatter compiles once per bucket; padded rows are zeros
            # aimed at the scratch block (garbage there is masked)
            width = 1
            while width < n:
                width *= 2
            if width > n:
                import jax.numpy as jnp
                pad = jnp.zeros((width - n,) + kw.shape[1:], kw.dtype)
                if device is not None:
                    import jax
                    pad = jax.device_put(pad, device)
                kw = jnp.concatenate([kw, pad], axis=0)
                vw = jnp.concatenate([vw, pad], axis=0)
                if quant:
                    spad = jnp.zeros((width - n,) + ksw.shape[1:],
                                     ksw.dtype)
                    if device is not None:
                        import jax
                        spad = jax.device_put(spad, device)
                    ksw = jnp.concatenate([ksw, spad], axis=0)
                    vsw = jnp.concatenate([vsw, spad], axis=0)
            idx = np.full((width,), SCRATCH_BLOCK, np.int32)
            idx[:n] = ids[:n]
            wire = (kw, vw, ksw, vsw) if quant else (kw, vw)
            self.arenas = self._adopt_scatter(width)(*self.arenas, *wire, idx)
        except BaseException:
            self.release(ids)
            raise
        return ids

    # -- introspection --------------------------------------------------- #
    def stats(self) -> dict:
        with self._lock:
            free = len(self._primary._free)
            classes = [c.stats() for c in self.classes]
        return {
            "classes": classes,
            "num_blocks": self.num_blocks,
            "block_len": self.block_len,
            "capacity": self.capacity,
            "free_blocks": free,
            "used_blocks": self.capacity - free,
            "utilization": ((self.capacity - free) / self.capacity
                            if self.capacity else 0.0),
            "arena_bytes": self.arena_bytes,
            "kv_quant": self.kv_quant or "none",
            # what a cached row is for the model at hand, and its bytes in
            # one layer as the arenas hold it
            "row": ("one latent row a position" if self.latent
                    else "a (k, v) pair a K/V head"),
            "row_lanes": self.n_heads * self.head_dim,
            "row_bytes": self.row_bytes,
            "window_blocks_released": self.window_released,
        }
