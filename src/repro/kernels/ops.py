"""Jit'd public wrappers for all Pallas kernels.

:func:`interpret_mode` is the one place that decides how a kernel runs:
compiled to Mosaic on a TPU backend, interpreted on the CPU backend (the
test lane), and refused anywhere else. The reference oracles live in
ref.py; tests sweep shapes/dtypes asserting equality between the two.

The host entries (``batch_*``) take (n, elems) uint8 rows, view them in
the swap kernels' word layout (``layout.to_words``: a row must be a
multiple of 512 B) and run one kernel call per batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .compress import block_dequantize, block_quantize
from .crc32c import fletcher_checksum
from .layout import from_words, pick_tile_rows, to_words
from .paged_attention import paged_decode_attention
from .swap_copy import gather_blocks, scatter_blocks
from .zero_detect import zero_detect


def interpret_mode() -> bool:
    """True on the CPU backend, False on TPU; any other backend raises,
    so a kernel never silently interprets where a chip was expected."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or interpreted "
                       f"on 'cpu'; the JAX backend is {backend!r}")


def batch_zero_detect(blocks: np.ndarray) -> np.ndarray:
    """(n, elems) uint8 host batch -> (n,) bool via the Pallas kernel
    (the backend's batched zero-page scan)."""
    words = to_words(blocks)
    out = zero_detect(jnp.asarray(words),
                      tile_rows=pick_tile_rows(words.shape[1]),
                      interpret=interpret_mode())
    return np.asarray(out)


def batch_checksum(blocks: np.ndarray) -> np.ndarray:
    """(n, elems) uint8 host batch -> (n,) uint32 Fletcher checksums.

    The device-path integrity tag for batched swaps; the host CRC stored
    in MS records remains zlib.crc32 so records are byte-compatible
    between scalar and batched paths.
    """
    words = to_words(blocks)
    out = fletcher_checksum(jnp.asarray(words),
                            tile_rows=pick_tile_rows(words.shape[1]),
                            interpret=interpret_mode())
    return np.asarray(out)


def batch_gather(pool: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Host entry for the swap-copy gather: ``out[i] = pool[indices[i]]``.

    ``pool`` is an (n_pool, elems) uint8 view of a physical MS frame; the
    indices are scalar-prefetched so the DMA engine knows each source
    block before its grid step (the device analogue of the EPT-walked
    batched swap-out copy).
    """
    out = gather_blocks(jnp.asarray(to_words(pool)),
                        jnp.asarray(indices, jnp.int32),
                        interpret=interpret_mode())
    return from_words(out, pool)


def batch_scatter(pool: np.ndarray, indices: np.ndarray,
                  blocks: np.ndarray) -> np.ndarray:
    """Host entry for the swap-copy scatter: ``pool[indices[i]] = blocks[i]``.

    Returns the updated pool as a host array (the device aliases the pool
    buffer in place; the host wrapper materializes the result for the
    caller to store back into the frame).
    """
    out = scatter_blocks(jnp.asarray(to_words(pool)),
                         jnp.asarray(indices, jnp.int32),
                         jnp.asarray(to_words(blocks)),
                         interpret=interpret_mode())
    return from_words(out, pool)


__all__ = [
    "zero_detect", "block_quantize", "block_dequantize",
    "fletcher_checksum", "gather_blocks", "scatter_blocks",
    "paged_decode_attention", "interpret_mode", "to_words", "from_words",
    "pick_tile_rows", "batch_zero_detect", "batch_checksum", "batch_gather",
    "batch_scatter",
]
