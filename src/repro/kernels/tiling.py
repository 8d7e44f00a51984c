"""Lane-dense layout shared by the compression kernels.

A flat vector of ``n`` elements is laid out as a ``(rows, 128)`` array whose
row count is padded to a whole number of ``(block_rows, 128)`` blocks.
``block_rows`` is a multiple of 32: the TPU tile of an int8 array is 32
sublanes × 128 lanes and that of a float32 array 8 × 128, so one layout
serves the float32 planes and the int8 sign plane alike. Small vectors get
one block of just enough rows; large ones stream in blocks of
``MAX_BLOCK_ROWS`` rows (256 KiB of float32 per buffer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128
ROW_TILE = 32
MAX_BLOCK_ROWS = 512


def block_rows(n: int) -> int:
    """Rows per grid step for an ``n``-element vector."""
    rows = -(-n // LANES)
    return min(MAX_BLOCK_ROWS, -(-rows // ROW_TILE) * ROW_TILE)


def to_tiles(flat: jax.Array, rows_per_block: int, fill) -> jax.Array:
    """``[n] → [n_blocks · rows_per_block, 128]``, padded with ``fill``."""
    n = flat.shape[0]
    per_block = rows_per_block * LANES
    pad = -(-n // per_block) * per_block - n
    return jnp.pad(flat, (0, pad), constant_values=fill).reshape(-1, LANES)


def from_tiles(tiles: jax.Array, n: int) -> jax.Array:
    """Inverse of `to_tiles`: the first ``n`` elements, flat."""
    return tiles.reshape(-1)[:n]
