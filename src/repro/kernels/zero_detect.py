"""Zero-block detection kernel (backend fast path, paper §4.2.2).

76.79% of swapped pages in production are zero pages (paper Fig 15c);
detecting them before compression is the hottest backend operation. The
kernel reduces each block tile-by-tile in VMEM; grid is over blocks, the
row dimension is tiled at ``tile_rows`` so arbitrarily large blocks (MPs
of a 9 MiB KV MS) never exceed VMEM.

Blocks arrive in the word layout of ``ops.to_words``: ``(n, R, 128)``
uint32, i.e. each block's bytes as R lane-dense rows of 128 words. A
block is zero when all its bytes are zero, so the scan reads words and
never relayouts bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANES, check_tile_rows


def _zero_detect_kernel(x_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.ones_like(out_ref)

    # OR-reduce the tile's words: int32 max over a != 0 mask stays in a
    # layout Mosaic reduces natively (no i1 vectors leave the compare)
    nz = jnp.max((x_ref[...] != 0).astype(jnp.int32))
    out_ref[...] = jnp.where(nz > 0, 0, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def zero_detect(words: jnp.ndarray, *, tile_rows: int,
                interpret: bool) -> jnp.ndarray:
    """words: (n, R, 128) uint32 -> (n,) bool (True == all zero).

    Grid (n, R // tile_rows); each step reduces one (tile_rows, 128)
    VMEM tile into the block's resident (1, 128) output row.
    """
    n, rows, lanes = words.shape
    assert lanes == LANES, words.shape
    check_tile_rows(rows, tile_rows)
    out = pl.pallas_call(
        _zero_detect_kernel,
        grid=(n, rows // tile_rows),
        in_specs=[pl.BlockSpec((None, tile_rows, LANES),
                               lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((None, 1, LANES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(words)
    return out[:, 0, 0] != 0
