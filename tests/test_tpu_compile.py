"""Compile-only guards for one v5e chip, described and not attached.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, VMEM over budget, programs over HBM. These tests compile
the main path's kernels at full widths (qwen3-4b's, and the held-expert
kernel at qwen3-235b-a22b's) and the full-width bf16 serving decode steps
of qwen3-4b and of qwen3-235b-a22b's EP8 share, so such a refusal shows up
here and not on the chip; and they hold the step to updating its KV pool
in place at every KV geometry. Nothing runs; the topology is described
inside a fixture only, and JAX's persistent cache is off around the
compiles (a compile for a described chip could be written to it but never
read back).
"""
import dataclasses
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.expert_swiglu import expert_swiglu
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


def _pool_ops(compiled, pool_shape):
    """Copies or dynamic-update-slices of a whole pool in ``compiled``."""
    pool = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    return re.findall(re.escape(pool) + r"\{[^}]*\} (copy|dynamic-update-slice)\(",
                      compiled.as_text())


def _step(shape, cfg, rows, sessions, max_seq):
    """The serving decode step of ``cfg`` compiled for one described chip
    at ``rows`` rows over a pool of ``sessions`` x ``max_seq`` tokens;
    returns it and the pool's shape."""
    def placed(tree):
        return jax.tree.map(lambda s: shape(s.shape, s.dtype), tree)
    params = placed(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    cache = placed(jax.eval_shape(lambda: M.init_cache(cfg, sessions, max_seq)))
    cache.update(block_table=shape((rows, max_seq // cfg.kv_block_tokens), jnp.int32),
                 kv_len=shape((rows,), jnp.int32))
    return (serve.make_decode_step(cfg).lower(
        params, shape((rows,), jnp.int32), cache).compile(), cache["kv_pool"].shape)


def test_full_width_decode_step_fits_one_chip(shape):
    cfg = serve.serving_config(get_config("qwen3-4b"))
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (36, 2560, 151936)
    compiled, pool_shape = _step(shape, cfg, B, B, MAX_SEQ)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    # the donated pool comes back in place: no copy of it, no second pool
    pool_bytes = 2 * int(np.prod(pool_shape))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 8, mem
    assert not _pool_ops(compiled, pool_shape)


@pytest.mark.parametrize("kv_heads,head_dim", [(8, 128), (4, 128), (2, 64)],
                         ids=["8x128", "4x128", "2x64"])
def test_decode_step_pool_stays_in_place(shape, kv_heads, head_dim):
    """qwen3-4b's step at 6 layers with only the KV geometry changed, 64
    rows over 256 sessions x 512 tokens: the donated pool is updated in
    place, with no whole-pool relayout and temp under an eighth of the
    pool."""
    cfg = dataclasses.replace(serve.serving_config(get_config("qwen3-4b")),
                              n_layers=6, n_kv_heads=kv_heads, head_dim=head_dim)
    compiled, pool_shape = _step(shape, cfg, 64, 256, 512)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool_shape))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 8, mem
    assert not _pool_ops(compiled, pool_shape)


MOE_CONFIG = Path(__file__).resolve().parents[1] / "bench" / "configs" / "qwen3-235b-a22b-ep8.json"


def _moe_arch():
    """qwen3-235b-a22b's EP8 share as the benchmark serves it: 12 layers,
    16 of 128 experts, an eighth of the vocabulary, published widths."""
    from repro.models.config import ArchConfig, MoEConfig

    c = json.loads(MOE_CONFIG.read_text())
    return ArchConfig(
        name=c["name"], family="moe", vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], qk_norm=True,
        moe=MoEConfig(n_routed=c["deployment"]["routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_ff_expert=c["moe_intermediate_size"],
                      held=(0, c["num_experts"])),
        param_dtype="bfloat16", compute_dtype="bfloat16")


@pytest.mark.parametrize("rows", [64, 256], ids=["window-64", "histories-256"])
def test_moe_share_decode_step_fits_one_chip(shape, monkeypatch, rows):
    """The held-expert step over the cell's pool (256 sessions x 512
    tokens) at the window's 64 rows and the set-up's 256: it fits one
    chip, runs the expert kernel and updates the pool in place."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)   # compile for the TPU
    cfg = _moe_arch()
    assert (cfg.d_model, cfg.moe.n_routed, cfg.moe.n_held, cfg.vocab) == (4096, 128, 16, 18992)
    compiled, pool_shape = _step(shape, cfg, rows, 256, 512)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    assert mem.alias_size_in_bytes >= 2 * int(np.prod(pool_shape))
    assert "expert_swiglu" in compiled.as_text()
    assert not _pool_ops(compiled, pool_shape)


def test_expert_kernel_compiles_at_full_width(shape):
    G, D, F, L = 16, 4096, 1536, 12
    _compile(lambda x, a, b, d, g, l: expert_swiglu(x, a, b, d, g, l, interpret=False),
             shape((752, D), jnp.bfloat16), shape((L, G, D, F), jnp.bfloat16),
             shape((L, G, D, F), jnp.bfloat16), shape((L, G, F, D), jnp.bfloat16),
             shape((G,), jnp.int32), shape((), jnp.int32))
