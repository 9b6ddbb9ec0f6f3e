"""Block quantize/dequantize kernels -- the device-side swap backend.

The paper's zswap backend compresses losslessly on host CPUs. A TPU has
no byte-granular entropy coder, so the TPU-native adaptation (DESIGN.md
§2, beyond-paper) is per-MP symmetric int8 quantization: 2x (bf16) / 4x
(f32) space saving with bounded error, acceptable for KV-cache blocks and
verified against the lossless host path in tests. Each grid step loads
one (1, mp_elems) MP tile into VMEM, computes its absmax scale on the
VPU, and writes the packed int8 tile -- compression at memory bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    scale_ref[...] = jnp.full_like(scale_ref, scale)


@functools.partial(jax.jit, static_argnames=("mps_per_block", "interpret"))
def block_quantize(blocks: jnp.ndarray, mps_per_block: int = 8,
                   *, interpret: bool):
    """blocks: (n, elems) float -> (q int8 (n, elems), scales (n, mps) f32).

    Grid: (n, mps_per_block); BlockSpec tiles one MP per step.
    """
    n, elems = blocks.shape
    assert elems % mps_per_block == 0
    mp = elems // mps_per_block
    q, scales = pl.pallas_call(
        _quant_kernel,
        grid=(n, mps_per_block),
        in_specs=[pl.BlockSpec((1, mp), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((1, mp), lambda i, j: (i, j)),
                   pl.BlockSpec((1, 1), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((n, elems), jnp.int8),
                   jax.ShapeDtypeStruct((n, mps_per_block), jnp.float32)],
        interpret=interpret,
    )(blocks)
    return q, scales


def _dequant_kernel(q_ref, scale_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32)
                  * scale_ref[0, 0]).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def block_dequantize(q: jnp.ndarray, scales: jnp.ndarray,
                     out_dtype=jnp.float32, *, interpret: bool):
    """Inverse kernel: (q (n, elems), scales (n, mps)) -> (n, elems)."""
    n, elems = q.shape
    mps = scales.shape[-1]
    mp = elems // mps
    return pl.pallas_call(
        _dequant_kernel,
        grid=(n, mps),
        in_specs=[pl.BlockSpec((1, mp), lambda i, j: (i, j)),
                  pl.BlockSpec((1, 1), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, mp), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, elems), out_dtype),
        interpret=interpret,
    )(q, scales)
