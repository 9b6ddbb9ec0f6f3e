"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, cell_skip_reason
from repro.configs.reduce import reduced_config
from repro.data.pipeline import SyntheticPipeline
from repro.models import model as M
from repro.optim import adamw
from repro.train import steps

RNG = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=32):
    pipe = SyntheticPipeline(cfg, B, S, seed=1)
    return {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = reduced_config(arch)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=10,
                                state_dtype=cfg.opt_dtype)
    state = steps.init_train_state(RNG, cfg, opt_cfg)
    batch = make_batch(cfg)
    state2, metrics = jax.jit(
        lambda s, b: steps.train_step(s, b, cfg, opt_cfg))(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), (arch, loss)
    assert float(metrics["grad_norm"]) > 0
    assert int(state2.step) == 1
    # a second step must reduce nothing to NaN
    state3, metrics3 = jax.jit(
        lambda s, b: steps.train_step(s, b, cfg, opt_cfg))(state2, batch)
    assert np.isfinite(float(metrics3["loss"]))
    # params actually changed
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(state.params),
                                jax.tree.leaves(state3.params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_output_shapes(arch):
    cfg = reduced_config(arch)
    params = M.init_params(RNG, cfg)
    batch = make_batch(cfg, B=2, S=32)
    hidden, aux = M.forward(params, cfg, batch, remat=False)
    assert hidden.shape == (2, 32, cfg.d_model)
    logits = M.logits_from_hidden(params, cfg, hidden)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ["qwen3-4b", "qwen2.5-32b", "granite-20b",
                                  "deepseek-moe-16b", "qwen3-moe-235b-a22b",
                                  "falcon-mamba-7b", "jamba-1.5-large-398b",
                                  "qwen3-4b:per_seq"])
def test_decode_matches_forward(arch):
    """Token-by-token paged decode == full teacher-forced forward
    (``<arch>:per_seq``: through the per-sequence pool layout)."""
    arch, _, layout = arch.partition(":")
    cfg = reduced_config(arch)
    if layout:
        cfg = dataclasses.replace(cfg, kv_pool_layout=layout)
    params = M.init_params(RNG, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(RNG, (B, S), 0, cfg.vocab)
    hidden, _ = M.forward(params, cfg, {"tokens": tokens}, remat=False)
    want = M.logits_from_hidden(params, cfg, hidden)
    cache = M.init_cache(cfg, B, S)
    got = []
    for t in range(S):
        lg, cache = M.decode_step(params, cfg, tokens[:, t], cache)
        got.append(lg)
    got = jnp.stack(got, axis=1)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 2e-3, (arch, rel)


def test_vlm_decode_with_vision_prefix():
    cfg = reduced_config("qwen2-vl-2b")
    params = M.init_params(RNG, cfg)
    B, S, nv = 2, 16, cfg.max_vision_tokens
    batch = make_batch(cfg, B=B, S=S)
    hidden, _ = M.forward(params, cfg, batch, remat=False)
    want = M.logits_from_hidden(params, cfg, hidden)
    cache = M.init_cache(cfg, B, S)
    got = []
    for t in range(S):
        ie = batch["vision_embeds"][:, t] if t < nv else None
        mp = batch["mrope_pos"][:, :, t : t + 1]
        lg, cache = M.decode_step(params, cfg, batch["tokens"][:, t], cache,
                                  mp, ie)
        got.append(lg)
    got = jnp.stack(got, axis=1)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 2e-3, rel


def test_remat_matches_no_remat():
    cfg = reduced_config("qwen3-4b")
    params = M.init_params(RNG, cfg)
    batch = make_batch(cfg)
    h1, _ = M.forward(params, cfg, batch, remat=True)
    h2, _ = M.forward(params, cfg, batch, remat=False)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-4, atol=2e-4)


def test_param_count_matches_actual():
    for arch in ("qwen3-4b", "deepseek-moe-16b", "falcon-mamba-7b"):
        cfg = reduced_config(arch)
        params = M.init_params(RNG, cfg)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        predicted = cfg.param_count()
        assert abs(actual - predicted) / actual < 0.01, \
            (arch, actual, predicted)


def test_encoder_only_has_no_decode_shapes():
    assert cell_skip_reason("hubert-xlarge", "decode_32k")
    assert cell_skip_reason("hubert-xlarge", "long_500k")
    assert cell_skip_reason("qwen3-4b", "long_500k")
    assert cell_skip_reason("falcon-mamba-7b", "long_500k") is None
    assert cell_skip_reason("jamba-1.5-large-398b", "long_500k") is None
