"""Swap-verification checksum kernel (paper §7.1).

HARDWARE ADAPTATION NOTE: CRC32 is a bit-serial polynomial
division -- its GF(2) shift-register structure maps to CPU lookup tables
or dedicated CRC instructions, neither of which exists on the TPU VPU
(8x128 vector lanes, no per-lane byte tables). Rather than force a
degenerate port (a 256-entry gather per byte), we implement the
*equivalent guarantee* -- detecting corrupted swap round-trips -- with a
weighted Fletcher checksum: two modular reductions (sum(x), sum(i*x)),
fully vectorizable, detecting all 1- and 2-byte errors and bursts up to
the weight period like Fletcher-32/Adler-32. The host control plane keeps
zlib.crc32 (paper-faithful); the device path uses this kernel; both are
exercised by the corruption-injection tests.

Rows arrive in the word layout of ``ops.to_words`` ((n, R, 128) uint32);
the kernel unpacks the four little-endian bytes of each word, so the
checksum is the byte checksum of ``ref.fletcher_checksum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANES, check_tile_rows

_P = 65521  # largest prime < 2^16


def _fletcher_kernel(x_ref, out_ref, *, tile_rows: int):
    j = pl.program_id(1)
    # all arithmetic in int32: every intermediate stays below 2^31
    x = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)  # (tile_rows, 128)
    word = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    # weight of byte b of word w in this tile: global byte index + 1
    base = (j * (tile_rows * LANES * 4)) % _P
    s1 = jnp.zeros(x.shape, jnp.int32)
    s2 = jnp.zeros(x.shape, jnp.int32)
    for b in range(4):
        xb = (x >> (8 * b)) & 0xFF
        w = (base + word * 4 + (b + 1)) % _P
        s1 = s1 + xb                                     # < 2^10
        s2 = s2 + (xb * w) % _P                          # < 2^18
    # fold rows then lanes, reducing mod p between (sums stay < 2^31
    # for tiles up to 2^13 rows)
    s1 = jnp.sum(jnp.sum(s1, axis=0, keepdims=True) % _P) % _P
    s2 = jnp.sum(jnp.sum(s2, axis=0, keepdims=True) % _P) % _P

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # combine tiles: modular add of the two 16-bit halves
    prev = out_ref[...]
    n1 = ((prev & 0xFFFF) + s1) % _P
    n2 = (((prev >> 16) & 0xFFFF) + s2) % _P    # int32 shift sign-extends
    out_ref[...] = n1 | (n2 << 16)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def fletcher_checksum(words: jnp.ndarray, *, tile_rows: int,
                      interpret: bool) -> jnp.ndarray:
    """words: (n, R, 128) uint32 -> (n,) uint32 byte checksums.

    Grid (n, R // tile_rows); each VMEM tile contributes a partial
    (s1, s2) pair combined modularly across tiles in the row's resident
    (1, 128) output block.
    """
    n, rows, lanes = words.shape
    assert lanes == LANES, words.shape
    check_tile_rows(rows, tile_rows)
    out = pl.pallas_call(
        functools.partial(_fletcher_kernel, tile_rows=tile_rows),
        grid=(n, rows // tile_rows),
        in_specs=[pl.BlockSpec((None, tile_rows, LANES),
                               lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((None, 1, LANES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(words)
    return out[:, 0, 0].astype(jnp.uint32)
