"""A mixture-of-experts family for the harness's tests: DeepSeekMoE-style
keys (arXiv:2401.06066) onto the program's MoE decoder, a leading dense
layer (``layer0``) then layers of shared and routed experts."""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.weights import DTYPES, key_from_seed


def arch_config(config: Dict, **overrides):
    from repro.models.config import ArchConfig, MoEConfig

    if int(config["first_k_dense_replace"]) > 1 or int(config["moe_layer_freq"]) != 1:
        raise ValueError("the program's MoE decoder has at most one leading "
                         "dense layer and experts in every layer after it")
    fields = dict(
        name=config["name"], family="moe",
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), d_ff=int(config["intermediate_size"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        moe=MoEConfig(n_routed=int(config["n_routed_experts"]),
                      top_k=int(config["num_experts_per_tok"]),
                      d_ff_expert=int(config["moe_intermediate_size"]),
                      n_shared=int(config["n_shared_experts"]),
                      first=int(config["first_k_dense_replace"])),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        kv_block_tokens=int(config["assumed"]["kv_block_tokens"]))
    fields.update(overrides)
    return ArchConfig(**fields)


def reduced(config: Dict) -> Dict:
    return config                       # already at CPU-test widths


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch):
    dt = DTYPES[arch.param_dtype]
    m = arch.moe
    V, D, H, KV, hd = arch.vocab, arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim_
    E, F, Fs = m.n_routed, m.d_ff_expert, m.n_shared * m.d_ff_expert
    L = arch.n_layers - m.first
    ks = iter(jax.random.split(key, 32))
    out_scale = 1.0 / math.sqrt(2 * arch.n_layers)

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(std, dt)

    def around_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)).astype(dt)

    def attn(*lead):
        return {"wq": normal((*lead, D, H * hd), D ** -0.5),
                "wk": normal((*lead, D, KV * hd), D ** -0.5),
                "wv": normal((*lead, D, KV * hd), D ** -0.5),
                "wo": normal((*lead, H * hd, D), (H * hd) ** -0.5 * out_scale)}

    def mlp(width, *lead):
        return {"w_gate": normal((*lead, D, width), D ** -0.5),
                "w_up": normal((*lead, D, width), D ** -0.5),
                "w_down": normal((*lead, width, D), width ** -0.5 * out_scale)}

    shared = mlp(Fs, L)
    params = {
        "embed": normal((V, D), D ** -0.5),
        "final_norm": around_one((D,)),
        "layers": {
            "ln1": around_one((L, D)), "ln2": around_one((L, D)), "attn": attn(L),
            "moe": {"router": normal((L, D, E), D ** -0.5),
                    **mlp(F, L, E),
                    **{f"shared_{k[2:]}": w for k, w in shared.items()}},
        },
    }
    if m.first:
        params["layer0"] = {"ln1": around_one((D,)), "ln2": around_one((D,)),
                            "attn": attn(), "mlp": mlp(arch.d_ff)}
    if not arch.tie_embeddings:
        params["lm_head"] = normal((D, V), D ** -0.5)
    return params


def make_params(arch, seed: int) -> Dict:
    return _make(key_from_seed(seed), arch)


def _attn_params(arch) -> int:
    D, H, KV, hd = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim_
    return 2 * D * H * hd + 2 * D * KV * hd


def layer_matmul_params(arch) -> int:
    """Weights one token's products read in an expert layer: attention,
    the router, its top-k routed experts and the shared ones."""
    m = arch.moe
    return (_attn_params(arch) + arch.d_model * m.n_routed
            + 3 * arch.d_model * m.d_ff_expert * (m.top_k + m.n_shared))


def param_count(arch) -> int:
    m, D = arch.moe, arch.d_model
    expert_layer = (_attn_params(arch) + 2 * D + D * m.n_routed
                    + 3 * D * m.d_ff_expert * (m.n_routed + m.n_shared))
    dense_layer = _attn_params(arch) + 2 * D + 3 * D * arch.d_ff
    head = 0 if arch.tie_embeddings else arch.vocab * D
    return (arch.vocab * D + head + D + m.first * dense_layer
            + (arch.n_layers - m.first) * expert_layer)


def kv_token_bytes(arch, dtype_bytes: int = 2) -> int:
    return arch.n_layers * 2 * arch.n_kv_heads * arch.head_dim_ * dtype_bytes


def decode_flops(arch, kv_lens: Sequence[int]) -> float:
    m, D = arch.moe, arch.d_model
    rows = len(kv_lens)
    dense_layer = _attn_params(arch) + 3 * D * arch.d_ff
    per_token = (m.first * dense_layer + (arch.n_layers - m.first) * layer_matmul_params(arch)
                 + D * arch.vocab)
    attn = 4.0 * arch.n_layers * arch.n_heads * arch.head_dim_ * float(
        sum(int(n) + 1 for n in kv_lens))
    return 2.0 * per_token * rows + attn


def decode_bytes(arch, kv_lens: Sequence[int], dtype_bytes: int = 2) -> float:
    """Every weight but the routed experts once, the routed experts the
    rows can reach (at most rows x top_k of each layer's), the rows'
    embeddings, the output head, and the K/V read and written."""
    m, D = arch.moe, arch.d_model
    rows = len(kv_lens)
    unreached = max(0, m.n_routed - rows * m.top_k)
    weights = (param_count(arch) - arch.vocab * D + rows * D
               - (arch.n_layers - m.first) * unreached * 3 * D * m.d_ff_expert)
    if arch.tie_embeddings:
        weights += arch.vocab * D             # the head reads the whole table
    kv = kv_token_bytes(arch, dtype_bytes) * (float(sum(int(n) for n in kv_lens)) + rows)
    return weights * dtype_bytes + kv
