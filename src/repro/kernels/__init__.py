"""Pallas TPU kernels for the perf-critical layers.

Each kernel module uses pl.pallas_call with explicit BlockSpec VMEM
tiling; ops.py exposes the host entries and ``interpret_mode`` (compiled
on TPU, interpreted on CPU), ref.py holds the pure-jnp oracles the tests
sweep against, and layout.py the word layout of the swap kernels.
Submodules load on import (``from repro.kernels import ops``), so the
jax-free host core can read ``layout`` without importing JAX.

  zero_detect      -- zero-block detection (backend fast path, Fig 15c)
  compress         -- per-MP int8 quantize/dequantize (device swap backend)
  crc32c           -- Fletcher checksum (swap verification, §7.1; see the
                      hardware-adaptation note for why not bit-serial CRC)
  swap_copy        -- batched block gather/scatter via scalar-prefetched
                      indirection (swap/compaction data path)
  paged_attention  -- decode attention walking the block table in-kernel
                      (the EPT walk on the I/O path)
"""
