"""The serving entry point end to end at reduced widths on the CPU:
requests decode through the jitted paged step, their KV lives in an
overcommitted Taiji cache whose batched swap path runs the Pallas
kernels (interpreted), and the run checks itself against the device
pool and the full-sequence forward pass."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import ml_dtypes
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
    bt = cfg.kv_block_tokens
    params = serve.init_params(cfg, 0)
    cache = M.init_cache(cfg, 2, 2 * bt)
    cache["kv_len"] = jnp.asarray([0, bt + 3], jnp.int32)   # row 1 in its 2nd block
    table = np.asarray(cache["block_table"])
    pool = cache["kv_pool"]
    logits, greedy, kv, new = serve.make_decode_step(cfg)(
        params, jnp.zeros((2,), jnp.int32), cache)
    assert pool.is_deleted()                 # donated: updated in place
    assert kv.shape == (2, 2, 2, cfg.n_kv_heads, cfg.head_dim_)
    got = np.array(new["kv_pool"])
    np.testing.assert_array_equal(got[:, table[0, 0], 0], np.asarray(kv[0]))
    np.testing.assert_array_equal(got[:, table[1, 1], 3], np.asarray(kv[1]))
    got[:, table[0, 0], 0] = 0
    got[:, table[1, 1], 3] = 0
    assert not got.any()                     # nothing else was written


# ------------------------------------------- the pool stays out of the scan
def _pool_cases():
    qwen = reduced_config("qwen3-4b")
    return {"dense": qwen,
            "moe_layer0": reduced_config("deepseek-moe-16b"),
            "hybrid": reduced_config("jamba-1.5-large-398b"),
            "per_seq": dataclasses.replace(qwen, kv_pool_layout="per_seq")}


def _scan_eqns(jaxpr):
    """Every ``scan`` equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scan_eqns(sub)


@pytest.mark.parametrize("case", ["dense", "moe_layer0", "hybrid", "per_seq"])
def test_decode_scan_carries_no_pool(case):
    """The layer scan may read the pool (a closed-over constant) but no
    carry, scanned input or stacked output holds one layer's pool or a
    stack of them (the whole pool, or all layers but the first): the pool
    is never sliced per layer nor rebuilt from the scan's outputs."""
    cfg = _pool_cases()[case]
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 4 * cfg.kv_block_tokens))
    assert ("layer0" in params) == (case == "moe_layer0")
    jaxpr = jax.make_jaxpr(lambda p, t, c: M.decode_step(p, cfg, t, c))(
        params, jax.ShapeDtypeStruct((2,), jnp.int32), cache)
    layer = cache["kv_pool"].shape[1:]
    scans = list(_scan_eqns(jaxpr.jaxpr))
    assert scans
    for eqn in scans:
        scanned = eqn.invars[eqn.params["num_consts"]:] + eqn.outvars
        shapes = {tuple(v.aval.shape) for v in scanned}
        assert not [s for s in shapes if s[-len(layer):] == layer], (case, shapes)


def test_decode_step_aliases_the_pool_parameter():
    cfg = serve.serving_config(reduced_config("qwen3-4b"))
    params = jax.eval_shape(lambda: serve.init_params(cfg, 0))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 4, 4 * cfg.kv_block_tokens))
    hlo = serve.make_decode_step(cfg).lower(
        params, jax.ShapeDtypeStruct((4,), jnp.int32), cache).compile().as_text()
    dims = ",".join(map(str, cache["kv_pool"].shape))
    entry = hlo[hlo.index("\nENTRY"):]
    param = re.search(r"= bf16\[" + dims + r"\]\S* parameter\((\d+)\)", entry)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    assert param and alias, hlo[:2000]
    assert f"({param.group(1)}, {{}}" in alias.group(1), alias.group(1)


# ------------------------------------------------ against a plain reference
def _ref_step(w, cfg, pool, table, pos, toks, kv_step):
    """A plain decode step in numpy (float32) over a paged numpy pool.

    Each layer computes its rows' K/V, writes the step's own bf16 K/V
    (``kv_step``, (B, L, 2, KV, hd)) at each row's slot of ``pool`` and
    attends over the row's blocks gathered from it. Returns the logits
    and the K/V it computed, for the caller to hold ``kv_step`` to."""
    H, KV, hd, bt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_, cfg.kv_block_tokens
    eps = cfg.norm_eps

    def norm(x, g):
        return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * g

    def rope(x, p):                          # x (B, heads, hd), p (B,)
        half = hd // 2
        ang = p[:, None, None] * (
            1.0 / cfg.rope_theta ** (np.arange(half, dtype=np.float32) / half))
        c, s = np.cos(ang), np.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    B, L = kv_step.shape[:2]
    g = H // KV
    x = w["embed"][toks]
    kv_ref = np.zeros(kv_step.shape, np.float32)
    lyr = w["layers"]
    for l in range(L):
        a = {k: v[l] for k, v in lyr["attn"].items()}
        h = norm(x, lyr["ln1"][l])
        q = rope(norm((h @ a["wq"]).reshape(B, H, hd), a["q_norm"]), pos)
        kv_ref[:, l, 0] = rope(norm((h @ a["wk"]).reshape(B, KV, hd),
                                    a["k_norm"]), pos)
        kv_ref[:, l, 1] = (h @ a["wv"]).reshape(B, KV, hd)
        o = np.zeros((B, H, hd), np.float32)
        for b in range(B):
            pool[l, table[b, pos[b] // bt], pos[b] % bt] = kv_step[b, l]
            blocks = pool[l, table[b, :pos[b] // bt + 1]].astype(np.float32)
            seq = blocks.reshape(-1, 2, KV, hd)[:pos[b] + 1]
            for hh in range(H):
                s = seq[:, 0, hh // g] @ q[b, hh] * hd ** -0.5
                p = np.exp(s - s.max())
                o[b, hh] = (p / p.sum()) @ seq[:, 1, hh // g]
        x = x + o.reshape(B, H * hd) @ a["wo"]
        m = {k: v[l] for k, v in lyr["mlp"].items()}
        h = norm(x, lyr["ln2"][l])
        gate = h @ m["w_gate"]
        x = x + (gate / (1 + np.exp(-gate)) * (h @ m["w_up"])) @ m["w_down"]
    return norm(x, w["final_norm"]) @ w["lm_head"], kv_ref


def test_decode_steps_match_a_plain_paged_reference():
    """Random 8-row batches over a pool of 12 sessions, rows crossing
    block boundaries: the step's logits and K/V match a plain paged
    decode, and the pool it leaves is, byte for byte, every token's K/V
    written at its own slot and nothing else."""
    cfg = serve.serving_config(reduced_config("qwen3-4b"))
    bt, S, B, cap = cfg.kv_block_tokens, 12, 8, 32
    params = serve.init_params(cfg, 1)
    w = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    dev = M.init_cache(cfg, S, cap)
    pool, table = dev["kv_pool"], np.asarray(dev["block_table"])
    ref_pool = np.zeros(pool.shape, ml_dtypes.bfloat16)
    kv_len = np.zeros(S, np.int32)
    step = serve.make_decode_step(cfg)
    rng = np.random.default_rng(5)
    for _ in range(24):
        ids = rng.choice(S, B, replace=False)
        toks = rng.integers(0, cfg.vocab, B).astype(np.int32)
        pos = kv_len[ids].copy()
        logits, _, kv, new = step(params, jnp.asarray(toks), {
            "kv_pool": pool, "block_table": jnp.asarray(table[ids]),
            "kv_len": jnp.asarray(pos)})
        pool, kv = new["kv_pool"], np.asarray(kv)
        want, kv_ref = _ref_step(w, cfg, ref_pool, table[ids], pos, toks, kv)
        np.testing.assert_allclose(kv.astype(np.float32), kv_ref,
                                   rtol=2 ** -7, atol=1e-6)
        np.testing.assert_allclose(np.asarray(logits, np.float32), want,
                                   rtol=1e-4, atol=1e-4 * np.abs(want).max())
        kv_len[ids] += 1
    assert kv_len.max() > bt                 # some row crossed into a 2nd block
    assert np.asarray(pool).tobytes() == ref_pool.tobytes()
