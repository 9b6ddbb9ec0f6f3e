"""The word layout every swap-path kernel reads and writes.

A TPU block's last two dims must be multiples of (8, 128) or equal the
array's, so a byte row of an MP cannot be a ``(1, bytes)`` block. The
swap kernels instead take each row's bytes as little-endian uint32 words
laid out ``(R, 128)``: one row of 128 words is 512 bytes, so a row's
byte count must be a multiple of ``ROW_BYTES``. A kernel that tiles the
R dim uses tiles of a multiple of 8 rows, or the whole row.

Row sizes this admits: any multiple of 512 B. A full-width qwen3-4b MP
(a 9 MiB KV MS over 8 MPs) is 1,179,648 B = 2304 rows of 128 words.
"""
from __future__ import annotations

import numpy as np

LANES = 128                    # words per layout row (the lane dim)
SUBLANES = 8                   # row-tile granularity (the sublane dim)
ROW_BYTES = LANES * 4          # bytes per layout row


def check_tile_rows(rows: int, tile_rows: int) -> None:
    """Raise unless ``tile_rows`` tiles ``rows`` in blocks the TPU takes."""
    if rows % tile_rows or (tile_rows % SUBLANES and tile_rows != rows):
        raise ValueError(
            f"tile of {tile_rows} rows cannot tile {rows} rows: it must "
            f"divide them and be a multiple of {SUBLANES} or all of them")


def pick_tile_rows(rows: int, cap: int = 512) -> int:
    """Largest multiple of 8 that divides ``rows`` and is at most ``cap``;
    the whole row when there is none (a row of under 8 layout rows)."""
    for t in range(min(cap, rows) // SUBLANES * SUBLANES, 0, -SUBLANES):
        if rows % t == 0:
            return t
    return rows


def to_words(rows: np.ndarray) -> np.ndarray:
    """(n, elems) host array of any dtype -> (n, R, 128) uint32 view of
    its bytes (a copy only where ``rows`` is not C-contiguous)."""
    rows = np.ascontiguousarray(rows)
    n = rows.shape[0]
    row_bytes = rows.nbytes // max(n, 1)
    if row_bytes % ROW_BYTES:
        raise ValueError(f"row of {row_bytes} B is not a multiple of "
                         f"{ROW_BYTES} B, the swap kernels' layout row")
    return rows.reshape(n, -1).view(np.uint32).reshape(n, -1, LANES)


def from_words(words: np.ndarray, like: np.ndarray) -> np.ndarray:
    """Inverse of :func:`to_words` for rows shaped and typed like ``like``."""
    return np.asarray(words).view(like.dtype).reshape(
        (words.shape[0],) + like.shape[1:])
