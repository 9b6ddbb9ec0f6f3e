"""FLOP and byte counts from shapes, the peak table, and the run's
refusal to measure anywhere but on a TPU."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import counts
from bench.cell import BENCH, arch_config
from bench.weights import make_params


def _arch(config_name, **kw):
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    return arch_config(config, **kw)


@pytest.mark.parametrize("config", ["qwen3-4b", "qwen2-0.5b"])
def test_param_count_matches_the_served_weights(config):
    arch = _arch(config)
    shapes = jax.eval_shape(lambda: make_params(arch, 0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert counts.param_count(arch) == n
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype(jnp.bfloat16)}


def test_published_sizes():
    q3 = _arch("qwen3-4b")
    q2 = _arch("qwen2-0.5b")
    # Qwen3-4B: 4.02B parameters with tied embeddings; 144 KiB of K/V a token
    assert counts.param_count(q3) == 4_022_468_096
    assert counts.kv_token_bytes(q3) == 36 * 2 * 8 * 128 * 2 == 147456
    # Qwen2-0.5B: 0.494B parameters; 12 KiB of K/V a token
    assert counts.param_count(q2) == 494_032_768
    assert counts.kv_token_bytes(q2) == 12288


def test_decode_counts_by_hand():
    arch = dataclasses.replace(_arch("qwen2-0.5b"),
                               vocab=10, d_model=8, n_layers=2, n_heads=2,
                               n_kv_heads=1, head_dim=4, d_ff=16)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert counts.layer_matmul_params(arch) == per_layer
    kv_lens = [3, 0]
    flops = 2 * (2 * per_layer + 8 * 10) * 2 + 4 * 2 * 2 * 4 * (4 + 1)
    assert counts.decode_flops(arch, kv_lens) == flops
    # tied: every weight but the table, the two rows' embeddings, the
    # table again as the head; K/V of 3 + 0 tokens read, 2 written
    weights = counts.param_count(arch) - 80 + 2 * 8 + 80
    kv = 2 * 2 * 1 * 4 * 2
    assert counts.decode_bytes(arch, kv_lens) == 2 * weights + kv * (3 + 2)


def test_swap_kernel_bytes():
    assert counts.swap_kernel_bytes("gather_blocks", 3, 2304) == 2 * 3 * 2304 * 512
    assert counts.swap_kernel_bytes("scatter_blocks", 1, 192) == 2 * 192 * 512
    assert counts.swap_kernel_bytes("zero_detect", 2, 192) == 2 * 192 * 512 + 2 * 512
    assert counts.swap_kernel_bytes("fletcher_checksum", 8, 2304) == 8 * 2304 * 512 + 8 * 512
    with pytest.raises(KeyError):
        counts.swap_kernel_bytes("paged_decode_attention", 1, 1)


def test_peak_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 1.97e14 and p["hbm_bytes_per_s"] == 8.19e11
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_run_refuses_a_host_without_a_tpu():
    from bench.run import device_info

    with pytest.raises(SystemExit) as e:
        device_info(jax, 1)
    assert "not a TPU" in str(e.value)
