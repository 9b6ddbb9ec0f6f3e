"""Dense decoders of the Qwen2 / Qwen3 kind: pre-norm blocks of
grouped-query attention (optional q/k/v bias, optional per-head q/k
RMSNorm) and a SwiGLU MLP, every layer alike, scanned over a leading
layer axis."""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.weights import DTYPES, key_from_seed


def arch_config(config: Dict, **overrides):
    """The program's ``ArchConfig`` for a configuration file, as served
    (weights in the file's ``torch_dtype``)."""
    from repro.models.config import ArchConfig

    arch = config["architecture"]
    heads = int(config["num_attention_heads"])
    fields = dict(
        name=config["name"], family="dense",
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or config["hidden_size"] // heads),
        d_ff=int(config["intermediate_size"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        kv_block_tokens=int(config["assumed"]["kv_block_tokens"]))
    fields.update(overrides)
    return ArchConfig(**fields)


def reduced(config: Dict) -> Dict:
    """``config`` at CPU-test widths: 3 layers of width 128, 4 heads over
    2 KV heads of 32, vocabulary 512, 8-token KV blocks."""
    cfg = dict(config, hidden_size=128, intermediate_size=256,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512)
    cfg["assumed"] = dict(config["assumed"], kv_block_tokens=8)
    return cfg


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, shape_key) -> Dict:
    """The tree the serving decode step reads (a dense decoder stack
    scanned over a leading layer axis); the plain reference reads the
    same tree. Matrices are normal with fan-in scaling, so logits stay
    near unit scale at any width; norms and biases are drawn too, so
    every parameter the architecture has takes part."""
    (V, D, L, H, KV, hd, F, tied, qkv_bias, qk_norm, dtype) = shape_key
    dt = DTYPES[dtype]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(std, dt)

    def around_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    out_scale = 1.0 / math.sqrt(2 * L)
    attn = {"wq": normal((L, D, H * hd), D ** -0.5),
            "wk": normal((L, D, KV * hd), D ** -0.5),
            "wv": normal((L, D, KV * hd), D ** -0.5),
            "wo": normal((L, H * hd, D), (H * hd) ** -0.5 * out_scale)}
    if qkv_bias:
        attn.update(bq=normal((L, H * hd), 0.1), bk=normal((L, KV * hd), 0.1),
                    bv=normal((L, KV * hd), 0.1))
    if qk_norm:
        attn.update(q_norm=around_one((L, hd)), k_norm=around_one((L, hd)))
    params = {
        "embed": normal((V, D), D ** -0.5),
        "final_norm": around_one((D,)),
        "layers": {
            "ln1": around_one((L, D)), "ln2": around_one((L, D)),
            "attn": attn,
            "mlp": {"w_gate": normal((L, D, F), D ** -0.5),
                    "w_up": normal((L, D, F), D ** -0.5),
                    "w_down": normal((L, F, D), F ** -0.5 * out_scale)},
        },
    }
    if not tied:
        params["lm_head"] = normal((D, V), D ** -0.5)
    return params


def make_params(arch, seed: int) -> Dict:
    """The served weights of ``arch`` from ``seed``."""
    shape_key = (arch.vocab, arch.d_model, arch.n_layers, arch.n_heads,
                 arch.n_kv_heads, arch.head_dim_, arch.d_ff,
                 arch.tie_embeddings, arch.qkv_bias, arch.qk_norm,
                 arch.param_dtype)
    return _make(key_from_seed(seed), shape_key)


# ------------------------------------------------------------------- counts
def _dims(arch):
    return (arch.vocab, arch.d_model, arch.n_layers, arch.n_heads,
            arch.n_kv_heads, arch.head_dim_, arch.d_ff)


def layer_matmul_params(arch) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    _, D, _, H, KV, hd, F = _dims(arch)
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def param_count(arch) -> int:
    V, D, L, H, KV, hd, _ = _dims(arch)
    per_layer = layer_matmul_params(arch) + 2 * D
    if arch.qkv_bias:
        per_layer += (H + 2 * KV) * hd
    if arch.qk_norm:
        per_layer += 2 * hd
    return V * D * (1 if arch.tie_embeddings else 2) + L * per_layer + D


def kv_token_bytes(arch, dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    return arch.n_layers * 2 * arch.n_kv_heads * arch.head_dim_ * dtype_bytes


def decode_flops(arch, kv_lens: Sequence[int]) -> float:
    """Model FLOPs of one decode step over rows whose caches hold
    ``kv_lens`` tokens before the step: the products with every weight
    matrix and the output head, and attention over kv_len + 1 positions."""
    V, D, L, H, _, hd, _ = _dims(arch)
    rows = len(kv_lens)
    dense = 2.0 * (L * layer_matmul_params(arch) + D * V) * rows
    attn = 4.0 * L * H * hd * float(sum(int(n) + 1 for n in kv_lens))
    return dense + attn


def decode_bytes(arch, kv_lens: Sequence[int], dtype_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once (the embedding
    table only for the rows' tokens, unless it is also the output head),
    the K/V of each row's ``kv_len`` tokens, and the K/V it writes."""
    V, D, _, _, _, _, _ = _dims(arch)
    rows = len(kv_lens)
    weights = param_count(arch) - V * D + rows * D
    if arch.tie_embeddings:
        weights += V * D                      # the head reads the whole table
    kv = kv_token_bytes(arch, dtype_bytes) * (float(sum(int(n) for n in kv_lens)) + rows)
    return weights * dtype_bytes + kv
