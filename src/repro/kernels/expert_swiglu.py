"""Grouped SwiGLU over the experts one device holds -- the decode step's
expert layer.

Rows arrive sorted by expert: group ``g`` is ``group_sizes[g]``
consecutive rows of ``x``, each a token routed to held expert ``g``, and
rows past the last group are not computed. One grid step is one
(row tile, F tile) pair of one group: it reads that expert's gate and up
columns and down rows for the F tile, and accumulates
``silu(x @ w_gate) * (x @ w_up) @ w_down`` for the tile's rows in VMEM.
A row tile that two groups share is visited once per group, the visits
consecutive, each storing only its own rows. A group with no rows is
never visited, so the weights of an expert no token reached are never
read: what a decode step needs of an expert layer whose tokens reach
only some of its experts.

The weights come as the whole stack of layers with the layer's index,
so a layer's experts are read in place, never sliced out into a copy.
The grid's length is the number of (group, row tile) visits, known only
on the device; the visit table and the layer are scalar-prefetched, as
in the ``megablox`` grouped matmul this kernel follows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 16              # rows of one tile (bf16 packs 16 rows a vreg)
VMEM_LIMIT = 64 * 1024 * 1024


def f_tile(d_ff: int) -> int:
    """Columns of the expert's hidden width one grid step reads."""
    for t in (256, 128):
        if d_ff % t == 0:
            return t
    return d_ff


def visits(group_sizes: jnp.ndarray, m: int, tm: int = ROW_TILE):
    """The grid's visit table for ``group_sizes`` over ``m`` rows:
    (group offsets (G+1,), group of each visit, row tile of each visit,
    number of visits). Visits run group by group, tiles ascending."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    most = m // tm + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                           total_repeat_length=most)
    start = jnp.cumsum(tiles) - tiles
    tile_ids = first[group_ids] + jnp.arange(most, dtype=jnp.int32) - start[group_ids]
    tile_ids = jnp.clip(tile_ids, 0, m // tm - 1).astype(jnp.int32)
    return offsets, group_ids, tile_ids, jnp.sum(tiles).astype(jnp.int32)


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, x_ref, wg_ref, wu_ref,
            wd_ref, out_ref, acc_ref, *, tm: int):
    del layer_ref
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(f == pl.num_programs(1) - 1)
    def _store():
        grp = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[grp]) & (row < offsets_ref[grp + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                  w_down: jnp.ndarray, group_sizes: jnp.ndarray,
                  layer: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Each group's rows through its expert's SwiGLU, in layer ``layer``.

    x: (m, D), m a multiple of ``ROW_TILE``, rows sorted by group;
    w_gate / w_up: (L, G, D, F); w_down: (L, G, F, D); group_sizes: (G,)
    int32 with sum <= m; layer: int32 scalar. Returns (m, D) in x's
    dtype; rows at and past ``sum(group_sizes)`` are left undefined.
    """
    m, D = x.shape
    _, G, _, F = w_gate.shape
    tm, tf = ROW_TILE, f_tile(F)
    assert m % tm == 0, (m, tm)
    offsets, group_ids, tile_ids, n = visits(group_sizes.astype(jnp.int32), m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, F // tf),
        in_specs=[
            pl.BlockSpec((tm, D), lambda v, f, l, o, g, t: (t[v], 0)),
            pl.BlockSpec((None, None, D, tf), lambda v, f, l, o, g, t: (l[0], g[v], 0, f)),
            pl.BlockSpec((None, None, D, tf), lambda v, f, l, o, g, t: (l[0], g[v], 0, f)),
            pl.BlockSpec((None, None, tf, D), lambda v, f, l, o, g, t: (l[0], g[v], f, 0)),
        ],
        out_specs=pl.BlockSpec((tm, D), lambda v, f, l, o, g, t: (t[v], 0)),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_swiglu",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, group_ids, tile_ids,
      x, w_gate, w_up, w_down)
