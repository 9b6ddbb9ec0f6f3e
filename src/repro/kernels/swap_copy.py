"""Batched block gather/scatter through an indirection table.

This is the swap engine's data path on device: moving a batch of MP-sized
blocks between pool slots according to the block table (swap-in
placement, compaction/defragmentation, prefetch). The block indices are
scalar-prefetched (``PrefetchScalarGridSpec``) so the DMA engine knows the
source block before the grid step runs -- the Pallas analogue of walking
the EPT before issuing the copy.

Blocks are in the word layout of ``ops.to_words``: a pool is
``(n_pool, R, 128)`` uint32 and one grid step moves one whole block, a
``(R, 128)`` tile whose dims equal the array's (leading dim squeezed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANES


def _gather_kernel(idx_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(pool: jnp.ndarray, indices: jnp.ndarray,
                  *, interpret: bool) -> jnp.ndarray:
    """out[i] = pool[indices[i]].

    pool: (n_pool, R, 128); indices: (n_out,) int32 -> (n_out, R, 128).
    The pool BlockSpec's index_map reads the prefetched indices.
    """
    n_out = indices.shape[0]
    _, rows, lanes = pool.shape
    assert lanes == LANES, pool.shape
    block = (None, rows, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out,),
        in_specs=[pl.BlockSpec(block, lambda i, idx: (idx[i], 0, 0))],
        out_specs=pl.BlockSpec(block, lambda i, idx: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, rows, LANES), pool.dtype),
        interpret=interpret,
    )(indices, pool)


def _scatter_kernel(idx_ref, pool_in_ref, blocks_ref, pool_ref):
    del pool_in_ref                       # aliased with pool_ref
    pool_ref[...] = blocks_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_blocks(pool: jnp.ndarray, indices: jnp.ndarray,
                   blocks: jnp.ndarray, *, interpret: bool
                   ) -> jnp.ndarray:
    """pool[indices[i]] = blocks[i]; returns the updated pool (donated).

    pool: (n_pool, R, 128); blocks: (n_out, R, 128). Uses input/output
    aliasing so untouched pool slots keep their data; the pool input
    stays in HBM (``pl.ANY``) since only the aliased output is written.
    """
    n_out, rows, lanes = blocks.shape
    assert lanes == LANES, blocks.shape
    block = (None, rows, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(block, lambda i, idx: (i, 0, 0))],
        out_specs=pl.BlockSpec(block, lambda i, idx: (idx[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},      # pool input (after prefetch) -> out
        interpret=interpret,
    )(indices, pool, blocks)
