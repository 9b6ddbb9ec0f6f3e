"""The serving entry point end to end at reduced widths on the CPU:
requests decode through the jitted paged step, their KV lives in an
overcommitted Taiji cache whose batched swap path runs the Pallas
kernels (interpreted), and the run checks itself against the device
pool and the full-sequence forward pass."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduce import reduced_config
from repro.launch import serve
from repro.models import model as M


@pytest.fixture(scope="module")
def served():
    cfg = serve.serving_config(reduced_config("qwen3-4b"))
    params = serve.init_params(cfg, 0)
    out = serve.run_serving(cfg, params, n_seqs=8, turns=4, batch=2,
                            prompt_len=24, gen_len=8, seed=3,
                            verbose=False)
    return cfg, params, out


def test_serving_config_keeps_training_default():
    cfg = reduced_config("qwen3-4b")
    assert cfg.param_dtype == "float32"
    assert serve.serving_config(cfg).param_dtype == "bfloat16"


def test_served_kv_reads_back_exact_through_swaps(served):
    _, _, out = served
    m = out["stats"]["metrics"]
    assert out["live_blocks"] >= 1.5 * out["phys_blocks"]
    assert m["ms_swapped_out"] > 0 and m["mp_swapped_in"] > 0
    assert m["swap_out_batches"] > 0             # the kernels' batched path
    assert out["kv_mismatched"] == []
    assert out["steps"] == (8 // 2) * 24 + 4 * 8       # steps of 2 requests


def test_decode_matches_forward_reference(served):
    cfg, params, out = served
    res = serve.check_against_reference(cfg, params, out, tol=2e-2)
    assert res["ok"], res
    assert res["greedy_checked"] > 0
    assert res["positions"] == len(out["tokens"]) == len(out["logits"])


def test_reference_check_catches_a_wrong_logit(served):
    cfg, params, out = served
    bad = dict(out, logits=out["logits"].copy())
    bad["logits"][-1, 0] += 1.0
    assert not serve.check_against_reference(cfg, params, bad, tol=2e-2)["ok"]


def test_serving_refuses_a_pin_set_larger_than_memory():
    cfg = serve.serving_config(reduced_config("qwen3-4b"))
    with pytest.raises(ValueError, match="cannot pin"):
        serve.run_serving(cfg, None, n_seqs=4, turns=2, batch=4,
                          prompt_len=8, gen_len=8, kv_overcommit=4.0,
                          verbose=False)


def test_serving_refuses_state_space_models():
    cfg = serve.serving_config(reduced_config("falcon-mamba-7b"))
    with pytest.raises(ValueError, match="attention-only"):
        serve.run_serving(cfg, None, n_seqs=2, turns=1, batch=2,
                          prompt_len=4, gen_len=4, verbose=False)


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-elsewhere"])
def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch, env_dir):
    import jax

    from repro import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == str(compile_cache.CHECKOUT_CACHE)
            assert compile_cache.CHECKOUT_CACHE.parent.joinpath(
                "chip_smoke.py").is_file()        # the checkout's root
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == saved[0]
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])


def test_decode_step_donates_the_pool():
    cfg = dataclasses.replace(
        serve.serving_config(reduced_config("qwen3-4b")), n_layers=2)
    params = serve.init_params(cfg, 0)
    cache = M.init_cache(cfg, 2, 2 * cfg.kv_block_tokens)
    pool = cache["kv_pool"]
    logits, greedy, kv, new = serve.make_decode_step(cfg)(
        params, jnp.zeros((2,), jnp.int32), cache)
    assert pool.is_deleted()                 # donated: updated in place
    assert kv.shape == (2, 2, 2, cfg.n_kv_heads, cfg.head_dim_)
    np.testing.assert_array_equal(np.asarray(new["kv_pool"][:, 0, 0]),
                                  np.asarray(kv[0]))
