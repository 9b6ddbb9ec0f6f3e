"""Paged decode attention kernel -- block-table indirection inside attn.

The device-side analogue of Taiji's EPT walk on the I/O path: every KV
read during decode goes through the block table, so swapped/compacted
blocks never require relayout of the pool. One grid step = one
(sequence, context-block) pair; the block index comes from the
scalar-prefetched block table; online-softmax state (m, l, acc) lives in
VMEM scratch across the context-block dimension.

Grid: (B, mbs). BlockSpecs: q (1, H, hd) resident per sequence; pool
block (1, bt, 2, KV, hd) selected by ``block_table[b, j]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(table_ref, kvlen_ref, q_ref, pool_ref, out_ref,
                       m_ref, l_ref, acc_ref, *, bt: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    mbs = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[b]
    block_start = j * bt

    @pl.when(block_start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (H, hd)
        kv = pool_ref[0]                                # (bt, 2, KV, hd)
        k = kv[:, 0].astype(jnp.float32)                # (bt, KV, hd)
        v = kv[:, 1].astype(jnp.float32)
        H, hd = q.shape
        KV = k.shape[1]
        g = H // KV
        qg = q.reshape(KV, g, hd)
        s = jnp.einsum("kgd,tkd->kgt", qg, k)           # (KV, g, bt)
        pos = block_start + jnp.arange(bt)
        s = jnp.where(pos[None, None, :] < kv_len, s, NEG_INF)

        m_prev = m_ref[...]                             # (KV, g)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jnp.einsum("kgt,tkd->kgd", p, v))
        m_ref[...] = m_new

    @pl.when(j == mbs - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / l[..., None]               # (KV, g, hd)
        KV, g, hd = out.shape
        out_ref[0] = out.reshape(KV * g, hd).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, kv_pool: jnp.ndarray,
                           block_table: jnp.ndarray, kv_len: jnp.ndarray,
                           *, interpret: bool) -> jnp.ndarray:
    """q: (B, H, hd); kv_pool: (n_blocks, bt, 2, KV, hd);
    block_table: (B, mbs) i32; kv_len: (B,) i32 -> (B, H, hd).

    NOTE on head layout: grouped heads are laid out KV-major, i.e.
    q[b].reshape(KV, g, hd) -- matching ref.paged_decode_attention.
    """
    B, H, hd = q.shape
    n_blocks, bt, two, KV, _ = kv_pool.shape
    assert two == 2 and H % KV == 0
    mbs = block_table.shape[1]
    g = H // KV
    scale = hd ** -0.5

    kern = functools.partial(_paged_attn_kernel, bt=bt, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # block_table, kv_len
        grid=(B, mbs),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, tbl, kvl: (b, 0, 0)),
            pl.BlockSpec((1, bt, 2, KV, hd),
                         lambda b, j, tbl, kvl: (tbl[b, j], 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, tbl, kvl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, g), jnp.float32),          # running max
            pltpu.VMEM((KV, g), jnp.float32),          # running denom
            pltpu.VMEM((KV, g, hd), jnp.float32),      # accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(block_table, kv_len, q, kv_pool)
