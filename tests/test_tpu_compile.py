"""Compile-only guards for one v5e chip, described and not attached.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, VMEM over budget, programs over HBM. These tests compile
the main path's five kernels at full qwen3-4b widths and the full-width
bf16 serving decode step, so such a refusal shows up here and not on the
chip. Nothing runs; the topology is described inside a fixture only, and
JAX's persistent cache is off around the compiles (a compile for a
described chip could be written to it but never read back).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.layout import ROW_BYTES, pick_tile_rows
from repro.launch import serve
from repro.models import model as M

V5E_HBM_BYTES = 15.75e9              # what the compiler lets one program use
MP_BYTES = 9 * 1024 * 1024 // 8      # one full-width qwen3-4b KV MP
ROWS = MP_BYTES // ROW_BYTES         # 2304 layout rows of 128 words
B, H, KV, HD, BT, MAX_SEQ = 4, 32, 8, 128, 64, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # parallel test workers each load libtpu to describe the chip
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_zero_detect_compiles_at_full_width(shape):
    _compile(lambda w: ops.zero_detect(w, tile_rows=pick_tile_rows(ROWS),
                                       interpret=False),
             shape((8, ROWS, 128), jnp.uint32))


def test_checksum_compiles_at_full_width(shape):
    _compile(lambda w: ops.fletcher_checksum(
        w, tile_rows=pick_tile_rows(ROWS), interpret=False),
        shape((8, ROWS, 128), jnp.uint32))


def test_gather_compiles_at_full_width(shape):
    _compile(lambda p, i: ops.gather_blocks(p, i, interpret=False),
             shape((8, ROWS, 128), jnp.uint32), shape((8,), jnp.int32))


def test_scatter_compiles_at_full_width(shape):
    _compile(lambda p, i, b: ops.scatter_blocks(p, i, b, interpret=False),
             shape((8, ROWS, 128), jnp.uint32), shape((3,), jnp.int32),
             shape((3, ROWS, 128), jnp.uint32))


def test_paged_attention_compiles_at_qwen3_4b_widths(shape):
    mbs = MAX_SEQ // BT
    _compile(lambda q, p, t, n: ops.paged_decode_attention(
        q, p, t, n, interpret=False),
        shape((B, H, HD), jnp.bfloat16),
        shape((B * mbs, BT, 2, KV, HD), jnp.bfloat16),
        shape((B, mbs), jnp.int32), shape((B,), jnp.int32))


def test_full_width_decode_step_fits_one_chip(shape):
    cfg = serve.serving_config(get_config("qwen3-4b"))
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (36, 2560, 151936)

    def placed(tree):
        return jax.tree.map(lambda s: shape(s.shape, s.dtype), tree)
    params = placed(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    cache = placed(jax.eval_shape(lambda: M.init_cache(cfg, B, MAX_SEQ)))
    compiled = serve.make_decode_step(cfg).lower(
        params, shape((B,), jnp.int32), cache).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    # the donated pool comes back in place: no copy of it, no second pool
    pool_bytes = cache["kv_pool"].size * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 8, mem
    pool = "bf16[" + ",".join(map(str, cache["kv_pool"].shape)) + "]"
    ops = re.findall(re.escape(pool) + r"\{[^}]*\} (copy|dynamic-update-slice)\(",
                     compiled.as_text())
    assert not ops, ops
