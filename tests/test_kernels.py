"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

The same kernels compile for a v5e chip in tests/test_tpu_compile.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# rows are elems * itemsize bytes; the swap kernels' word layout takes
# multiples of 512 B (layout.ROW_BYTES), tiled in multiples of 8 rows
@pytest.mark.parametrize("n,elems,tile_rows", [(4, 8192, 8), (8, 4096, None),
                                               (3, 512, None), (16, 1024, None)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int8])
def test_zero_detect_sweep(n, elems, tile_rows, dtype):
    x = RNG.standard_normal((n, elems)).astype(dtype)
    x[::3] = 0
    words = ops.to_words(x)
    tile_rows = tile_rows or words.shape[1]
    got = ops.zero_detect(jnp.asarray(words), tile_rows=tile_rows,
                          interpret=True)
    want = ref.zero_detect(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,elems,mps", [(2, 512, 4), (4, 1024, 8),
                                         (1, 2048, 16), (6, 768, 3)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quantize_roundtrip_sweep(n, elems, mps, dtype):
    x = (RNG.standard_normal((n, elems)) * 4).astype(dtype)
    x[0, :elems // mps] = 0                       # a zero MP
    q, s = ops.block_quantize(jnp.asarray(x), mps, interpret=True)
    qr, sr = ref.block_quantize(jnp.asarray(x), mps)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    d = ops.block_dequantize(q, s, interpret=True)
    # bounded quantization error (beyond-paper lossy KV backend contract)
    assert np.abs(np.asarray(d) - x.astype(np.float32)).max() <= \
        np.abs(x).max() / 127.0 + 1e-6


def _checksum(b, tile_rows=None):
    words = ops.to_words(b)
    return np.asarray(ops.fletcher_checksum(
        jnp.asarray(words), tile_rows=tile_rows or words.shape[1],
        interpret=True))


@pytest.mark.parametrize("n,elems,tile_rows", [(4, 16384, 8), (2, 512, None),
                                               (8, 2048, None)])
def test_fletcher_sweep_and_sensitivity(n, elems, tile_rows):
    b = RNG.integers(0, 256, (n, elems)).astype(np.uint8)
    got = _checksum(b, tile_rows)
    want = ref.fletcher_checksum(jnp.asarray(b))
    np.testing.assert_array_equal(got, np.asarray(want))
    # single-byte corruption always detected; swap of two adjacent bytes too
    b2 = b.copy()
    b2[0, 7] ^= 1
    assert _checksum(b2)[0] != got[0]
    b3 = b.copy()
    if b3[1, 10] != b3[1, 11]:
        b3[1, 10], b3[1, 11] = b[1, 11], b[1, 10]
        assert _checksum(b3)[1] != got[1]


@pytest.mark.parametrize("n_pool,elems,n_out", [(16, 512, 4), (8, 256, 8),
                                                (32, 1024, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gather_scatter_sweep(n_pool, elems, n_out, dtype):
    pool = RNG.standard_normal((n_pool, elems)).astype(dtype)
    idx = RNG.choice(n_pool, size=n_out, replace=False).astype(np.int32)
    got = ops.gather_blocks(jnp.asarray(ops.to_words(pool)), jnp.asarray(idx),
                            interpret=True)
    np.testing.assert_array_equal(ops.from_words(got, pool),
                                  np.asarray(ref.gather_blocks(pool, idx)))
    blocks = RNG.standard_normal((n_out, elems)).astype(dtype)
    got2 = ops.scatter_blocks(jnp.asarray(ops.to_words(pool)),
                              jnp.asarray(idx),
                              jnp.asarray(ops.to_words(blocks)),
                              interpret=True)
    want2 = ref.scatter_blocks(jnp.asarray(pool), jnp.asarray(idx),
                               jnp.asarray(blocks))
    np.testing.assert_array_equal(ops.from_words(got2, pool),
                                  np.asarray(want2))


@pytest.mark.parametrize("B,H,KV,hd,bt,mbs", [
    (2, 8, 2, 32, 8, 4),
    (1, 4, 4, 64, 16, 2),      # MHA
    (3, 16, 1, 32, 8, 3),      # MQA
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_paged_attention_sweep(B, H, KV, hd, bt, mbs, dtype):
    q = RNG.standard_normal((B, H, hd)).astype(dtype)
    pool = RNG.standard_normal((B * mbs + 2, bt, 2, KV, hd)).astype(dtype)
    # non-trivial block table: blocks assigned in random pool order
    perm = RNG.permutation(B * mbs).astype(np.int32) + 2
    table = perm.reshape(B, mbs)
    kvlen = RNG.integers(1, mbs * bt + 1, (B,)).astype(np.int32)
    got = ops.paged_decode_attention(jnp.asarray(q), jnp.asarray(pool),
                                     jnp.asarray(table), jnp.asarray(kvlen),
                                     interpret=True)
    want = ref.paged_decode_attention(jnp.asarray(q), jnp.asarray(pool),
                                      jnp.asarray(table), jnp.asarray(kvlen))
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
