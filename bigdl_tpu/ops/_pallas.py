"""What the Pallas modules share that belongs to none of them: whether a
call runs interpreted, a dtype's sublane tile and the check of a block's rows
against it, and the layer index of an arena handed over whole or a layer at
a time."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def sublane_tile(dtype) -> int:
    """Rows of ``dtype`` a sublane tile holds: 8 of 32 bits, 16 of 16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def check_block_rows(block_len: int, dtype) -> None:
    """Raise where a COMPILED kernel cannot take the pool's blocks: each
    grid step copies one (block_len, W) block to a dynamic row of a VMEM
    buffer, and Mosaic wants that row aligned to the dtype's sublane tile
    (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit)."""
    tile = sublane_tile(dtype)
    if block_len % tile:
        raise ValueError(
            f"decode_attn='paged_kernel' needs block_len to be a multiple "
            f"of {tile} for {jnp.dtype(dtype).name} KV blocks on TPU "
            f"(got block_len={block_len})")


def arena_layer(arena, layer):
    """A lone layer's arena (N, B, W) is the whole arena's layer 0."""
    if arena.ndim == 3:
        return arena[None], 0
    return arena, layer
