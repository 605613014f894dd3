"""Lane-dense tiling shared by the codec kernels.

A 1-D vector of n elements is laid out as ``(rows, 128)`` — every
(8, 128) f32 register tile full, instead of the one live sublane a
``(1, n)`` row gives — and streamed through VMEM in blocks of
``ROW_BLOCK`` rows, so a kernel's VMEM use is bounded by the block and
no longer grows with n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128        # TPU lane width
SUBLANE = 8       # f32 rows of an (8, 128) register tile
ROW_BLOCK = 512   # rows of 128 lanes per grid step (a 256 KiB f32 tile)


def column_rows(m: int) -> int:
    """S, the rows of 128 lanes an m-long column of the SCD solvers'
    stack is laid out in: a whole number of (8, 128) tiles, so that
    every DMA of a column, or of a tile of its rows, moves whole tiles
    (the row-tiled SCD kernel hung on a TPU v5e while its last tile's
    DMAs moved 1,970 rows, and runs with 1,976)."""
    return -(-m // (SUBLANE * LANE)) * SUBLANE


def row_tiles(n: int) -> tuple[int, int]:
    """``(rows per block, padded row count)`` for n elements. A vector
    that fits one block is one block of exactly its own rows (a block
    equal to the whole array needs no (8, 128) alignment)."""
    rows = max(1, -(-n // LANE))
    block = min(rows, ROW_BLOCK)
    return block, -(-rows // block) * block


def to_rows(x: jax.Array, rows: int) -> jax.Array:
    """``(..., n)`` -> ``(..., rows, 128)``, zero-padded at the tail."""
    pad = rows * LANE - x.shape[-1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (rows, LANE))
