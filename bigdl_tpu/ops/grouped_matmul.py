"""The routed experts' grouped matmul of a decode-sized round as a Pallas TPU
kernel.

``parallel.expert.grouped_swiglu`` multiplies rows SORTED by expert with the
stacked expert matrices ``(E, K, N)``: rows ``[offs[e], offs[e + 1])`` meet
expert ``e``'s matrix.  In a decode, verify or self-drafting round the rows
are few (256-1,024 assignments, 1-16 an expert) and the matrices are the
bytes: the round is as fast as the HIT experts' matrices stream from HBM.
``lax.ragged_dot`` on the chip reads only the hit experts too, but multiplies
a ROW TILE of up to 512 rows for every hit expert (its tiling is ``(rows up
to 512, 512, 256-512)``): near the roofline where the tile is Laguna's 64
rows, at 37% of it at GLM's 512 and at 25% at Solar's (PERF.md, PR 41).  This
kernel is a weight-streaming one, in the manner of ``ops.latent_attention``:

- the matrices stay in HBM, whole (``memory_space=ANY``), and are never
  copied, concatenated or re-laid out; the hit experts in order, their number
  and every expert's row offset are scalar-prefetch operands, derived from
  ``sizes`` in XLA (:func:`hit_experts`);
- the grid is the tiles of the output's width; inside a tile a loop walks the
  HIT experts only, with hand-issued asynchronous copies of an expert's ``(K,
  tile)`` columns into one of two VMEM buffers while the expert before
  computes on the other; a tile's last expert issues the next tile's first
  copy.  An expert's columns are fetched ONCE a call, an expert without rows
  costs nothing, and with no expert hit nothing is fetched at all;
- the rows are small beside the matrices (2-8 MB) and lie in VMEM whole.  An
  expert's run of rows starts anywhere; the kernel reads WINDOWS of
  ``window`` rows from the sublane tile its run starts in and stores a
  window's product under a mask of the expert's own rows (masked windows, as
  ``jax.experimental.pallas.ops.tpu.megablox`` has them; no re-ranking of the
  rows into expert-aligned tiles, which would cost the caller a second gather
  and a padded copy of the rows).  A neighbour's rows in the window are
  multiplied and dropped, rows past ``sum(sizes)`` never reach a stored row
  (a matmul keeps rows apart), and a run that fits one window is ONE pass of
  the expert's columns through the MXU whatever its length: a pass costs
  what streaming the columns in does, not what the rows do.

Two matrices in one call (``w = (w_gate, w_up)``) give ``silu(x w_gate) * (x
w_up)``: the rows are read once, both products are formed in VMEM and only
their product is written.  The arithmetic is ``grouped_swiglu``'s as written:
operands in the arrays' dtype, float32 accumulation, each product cast to the
rows' dtype before anything else is done with it.

The output's rows that no expert owns are zeros.  ``lax.ragged_dot`` is the
CPU's path and this kernel's oracle (``tests/test_grouped_matmul.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import _pallas
from bigdl_tpu.ops._pallas import sublane_tile
from bigdl_tpu.ops.latent_attention import LANES

#: rows a window holds: one pass of an expert's columns through the MXU.  The
#: copies bound a call, not the passes: 16 / 32 / 64 / 128 rows read alike on
#: the chip (PERF.md, PR 41: GLM's verify round's SwiGLU alone 1.626 / 1.624 /
#: 1.632 / 1.634 ms, 89% of its hit bytes' roofline; Solar's, Ling's and
#: Laguna's rounds and 4,096 rows within 1%); 64 holds a whole run at every
#: cell's rows an expert, so a hot expert is still one pass
WINDOW = 64
#: bytes of ONE matrix's columns a copy brings (the same sweep, 2 / 4 MB: GLM's
#: SwiGLU 1.69 / 1.63 ms, Laguna's 8 hit experts 0.267 / 0.245, Solar's and
#: Ling's alike; 1 MB no better where it was tried)
TILE_BYTES = 4 << 20


def column_tile(k: int, n: int, itemsize: int,
                tile_bytes: int = TILE_BYTES) -> int:
    """The widest whole-lane divisor of ``n`` whose ``(k, tile)`` columns fit
    ``tile_bytes`` (``n`` itself where it has no whole-lane divisor: the
    interpreter's toy widths)."""
    fits = [t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and k * t * itemsize <= tile_bytes]
    if fits:
        return fits[-1]
    return LANES if n % LANES == 0 else n


def hit_experts(sizes):
    """What the kernel prefetches, from ``sizes`` (E,): the hit experts in
    order (behind them the missed ones, never read), how many are hit, and
    every expert's first row."""
    ids = jnp.argsort(sizes == 0, stable=True)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(sizes, dtype=jnp.int32)])
    return (ids.astype(jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32).reshape(1), offs)


def window_rows(rows: int, dtype, window: int = WINDOW) -> int:
    """The window a call over ``rows`` rows reads: whole sublane tiles, and
    no more than the rows (padded to whole tiles) hold."""
    tile = sublane_tile(dtype)
    return max(tile, min(int(window), -(-rows // tile) * tile) // tile * tile)


def row_tiles(sizes, rows: int, dtype, window: int = WINDOW):
    """How many windows a call over ``rows`` rows with these ``sizes`` visits
    for ONE tile of the output's width: an expert's run of ``n`` rows
    starting ``a`` rows into its sublane tile takes ``ceil((a + n) /
    window)``."""
    tile, window = sublane_tile(dtype), window_rows(rows, dtype, window)
    lo = jnp.cumsum(sizes, dtype=jnp.int32) - sizes
    span = lo % tile + sizes
    return jnp.sum(jnp.where(sizes > 0, -(-span // window), 0),
                   dtype=jnp.int32)


def _kernel(ids_ref, hit_ref, offs_ref, x_ref, *refs, n_w: int, window: int,
            tile: int, tn: int):
    w_refs, o_ref = refs[:n_w], refs[n_w]
    bufs, sem, turn = refs[n_w + 1:2 * n_w + 1], refs[-2], refs[-1]
    j, tiles = pl.program_id(0), pl.num_programs(0)
    hit, rows = hit_ref[0], x_ref.shape[0]

    def copies(g, col, b, act):
        cols = pl.ds(pl.multiple_of(col * tn, tn), tn)
        for i in range(n_w):
            act(pltpu.make_async_copy(w_refs[i].at[ids_ref[g], :, cols],
                                      bufs[i].at[b], sem.at[i, b]))

    def start(g, col, b):
        copies(g, col, b, lambda c: c.start())

    @pl.when(j == 0)
    def _():
        turn[0] = 0             # the buffer the next expert computes on

        @pl.when(hit > 0)
        def _():
            start(0, 0, 0)

    o_ref[...] = jnp.zeros_like(o_ref)

    def expert(g, b):
        last = g + 1 == hit

        @pl.when(jnp.logical_not(last))
        def _():
            start(g + 1, j, 1 - b)

        # a tile's last expert issues the next tile's first copy
        @pl.when(last & (j + 1 < tiles))
        def _():
            start(0, j + 1, 1 - b)

        copies(g, j, b, lambda c: c.wait())
        e = ids_ref[g]
        lo, hi = offs_ref[e], offs_ref[e + 1]
        base = lo // tile * tile

        def one(i, _):
            # (the last window is pulled back inside the rows: what it reads
            # twice it stores twice, alike)
            r0 = pl.multiple_of(jnp.minimum(base + i * window, rows - window),
                                tile)
            at = pl.ds(r0, window)
            x = x_ref[at, :]
            prod = [jnp.dot(x, buf[b], preferred_element_type=jnp.float32)
                    .astype(o_ref.dtype).astype(jnp.float32) for buf in bufs]
            y = prod[0] if n_w == 1 else jax.nn.silu(prod[0]) * prod[1]
            row = r0 + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            own = (row >= lo) & (row < hi)
            o_ref[at, :] = jnp.where(own, y, o_ref[at, :].astype(jnp.float32)
                                     ).astype(o_ref.dtype)

        lax.fori_loop(0, (hi - base + window - 1) // window, one, None)
        return 1 - b

    turn[0] = lax.fori_loop(0, hit, expert, turn[0])


def grouped_matmul(x, w, sizes, *, window: int = WINDOW,
                   tile_bytes: int = TILE_BYTES, interpret=None):
    """``x`` (M, K) rows sorted by expert against ``w`` (E, K, N) -- or a
    pair of such, for ``silu(x w[0]) * (x w[1])`` -- with ``sizes`` (E,)
    int32 rows an expert: -> (M, N) in ``x``'s dtype, rows past
    ``sum(sizes)`` zeros.  ``window`` rows a pass (whole sublane tiles),
    ``tile_bytes`` of a matrix's columns a copy."""
    ws = tuple(w) if isinstance(w, (tuple, list)) else (w,)
    m, k = x.shape
    e, _, n = ws[0].shape
    if any(a.shape != (e, k, n) or a.dtype != x.dtype for a in ws):
        raise ValueError(f"expert matrices {[a.shape for a in ws]} of "
                         f"{[a.dtype for a in ws]} do not meet rows "
                         f"{x.shape} of {x.dtype}")
    if interpret is None:
        interpret = _pallas.use_interpret()
    ids, hit, offs = hit_experts(sizes.astype(jnp.int32))
    xp = jnp.pad(x, ((0, -m % sublane_tile(x.dtype)), (0, 0)))
    out = _call(xp, ws, ids, hit, offs, window=window_rows(m, x.dtype, window),
                tn=column_tile(k, n, x.dtype.itemsize, int(tile_bytes)),
                interpret=bool(interpret))
    return out[:m]


@functools.partial(jax.jit, static_argnames=("window", "tn", "interpret"))
def _call(x, ws, ids, hit, offs, *, window, tn, interpret):
    """The kernel's call, a jitted function of its own: the routed layers of
    a step program that multiply alike are traced and lowered ONCE, which a
    warm start pays again every time (``ops.grouped_attention._attend``)."""
    m, k = x.shape
    n, n_w, size = ws[0].shape[2], len(ws), x.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn,),
        in_specs=[pl.BlockSpec((m, k), lambda j, *_: (0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_w,
        out_specs=pl.BlockSpec((m, tn), lambda j, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((2, k, tn), x.dtype)] * n_w + [
            pltpu.SemaphoreType.DMA((n_w, 2)),
            pltpu.SMEM((1,), jnp.int32)])
    # the rows and a tile of the output, twice each (the pipeline's buffers),
    # the matrices' columns twice each, and a window's float32 products
    vmem = (2 * m * k * size + 2 * m * tn * size + 2 * n_w * k * tn * size
            + 8 * window * (k + 2 * tn) * 4 + (8 << 20))
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, window=window,
                          tile=sublane_tile(x.dtype), tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="grouped_matmul",
    )(ids, hit, offs, x, *ws)
