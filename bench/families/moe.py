"""Mixture-of-experts decoders of the Qwen3-MoE kind, as one device of an
expert-parallel deployment holds them: the Qwen3 attention block, then
in every layer a softmax router over all ``routed_experts`` experts,
top-k gates renormalised over the k, and SwiGLU experts of which this
device holds ``num_experts``, from ``held_experts_first`` on (the
configuration's ``deployment`` group). Assignments to experts held
elsewhere add nothing here, in the program and in the reference alike.

The counts are this device's share of the work: attention, the router
and the LM-head slice in full; a held expert as far as the step's rows
reach it. With ``rows`` tokens each routed to ``k`` of ``E`` experts, a
given expert is missed by all of them with probability
``(1 - k/E) ** rows`` under uniform routing (a token's k experts are k
distinct of E), so ``reach = 1 - (1 - k/E) ** rows`` of each held
expert's weights is read in expectation, and each token computes
``k * held / E`` held experts on average. Random router weights route
near uniformly; skewed routing reaches fewer experts, so no count
exceeds what a perfect implementation must move.
"""
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
    from repro.models.config import ArchConfig, MoEConfig

    if (int(config["decoder_sparse_step"]) != 1 or config["mlp_only_layers"]
            or not config["norm_topk_prob"]):
        raise ValueError("the family needs experts in every layer and top-k "
                         "gates renormalised over the k")
    dep, arch = config["deployment"], config["architecture"]
    first = int(dep["held_experts_first"])
    fields = dict(
        name=config["name"], family="moe",
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), d_ff=0,
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        moe=MoEConfig(n_routed=int(dep["routed_experts"]),
                      top_k=int(config["num_experts_per_tok"]),
                      d_ff_expert=int(config["moe_intermediate_size"]),
                      held=(first, first + int(config["num_experts"]))),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        kv_block_tokens=int(config["assumed"]["kv_block_tokens"]))
    fields.update(overrides)
    return ArchConfig(**fields)


def reduced(config: Dict) -> Dict:
    """``config`` at CPU-test widths: width 128, 4 heads over 2 KV heads
    of 32, experts of width 64, vocabulary 512, 8-token KV blocks. The
    depth, the router's experts, the experts held and the top-k are
    kept, so routing, and what a near tie in it does to the logits, is
    as at full width."""
    cfg = dict(config, hidden_size=128, moe_intermediate_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               vocab_size=512)
    cfg["assumed"] = dict(config["assumed"], kv_block_tokens=8)
    return cfg


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, shape_key) -> Dict:
    """The tree the serving decode step reads (a stack scanned over a
    leading layer axis); the plain reference reads the same tree.
    Matrices are normal with fan-in scaling; norms are drawn too. Each
    layer, and each expert in it, is drawn on its own, so the
    temporaries stay one expert's size beside the weights."""
    (V, D, L, H, KV, hd, E, Eh, F, tied, qk_norm, dtype) = shape_key
    dt = DTYPES[dtype]
    out_scale = 1.0 / math.sqrt(2 * L)
    k_embed, k_head, k_norm, k_attn, k_router, k_experts = jax.random.split(key, 6)

    def normal(k, shape, std):
        return jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)

    def around_one(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def attn(k):
        ks = jax.random.split(k, 6)
        p = {"wq": normal(ks[0], (D, H * hd), D ** -0.5),
             "wk": normal(ks[1], (D, KV * hd), D ** -0.5),
             "wv": normal(ks[2], (D, KV * hd), D ** -0.5),
             "wo": normal(ks[3], (H * hd, D), (H * hd) ** -0.5 * out_scale)}
        if qk_norm:
            p.update(q_norm=around_one(ks[4], (hd,)), k_norm=around_one(ks[5], (hd,)))
        return p

    def expert(k):
        ks = jax.random.split(k, 3)
        return {"w_gate": normal(ks[0], (D, F), D ** -0.5),
                "w_up": normal(ks[1], (D, F), D ** -0.5),
                "w_down": normal(ks[2], (F, D), F ** -0.5 * out_scale)}

    kn = jax.random.split(k_norm, 3)
    experts = jax.lax.map(expert, jax.random.split(k_experts, L * Eh))
    moe = {n: w.reshape(L, Eh, *w.shape[1:]) for n, w in experts.items()}
    moe["router"] = jax.lax.map(lambda k: normal(k, (D, E), D ** -0.5),
                                jax.random.split(k_router, L))
    params = {
        "embed": normal(k_embed, (V, D), D ** -0.5),
        "final_norm": around_one(kn[0], (D,)),
        "layers": {"ln1": around_one(kn[1], (L, D)), "ln2": around_one(kn[2], (L, D)),
                   "attn": jax.lax.map(attn, jax.random.split(k_attn, L)),
                   "moe": moe},
    }
    if not tied:
        params["lm_head"] = normal(k_head, (D, V), D ** -0.5)
    return params


def make_params(arch, seed: int) -> Dict:
    """The served weights of ``arch`` from ``seed``."""
    m = arch.moe
    shape_key = (arch.vocab, arch.d_model, arch.n_layers, arch.n_heads,
                 arch.n_kv_heads, arch.head_dim_, m.n_routed, m.n_held,
                 m.d_ff_expert, arch.tie_embeddings, arch.qk_norm,
                 arch.param_dtype)
    return _make(key_from_seed(seed), shape_key)


# ------------------------------------------------------------------- counts
def _attn_params(arch) -> int:
    D, H, KV, hd = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim_
    return 2 * D * H * hd + 2 * D * KV * hd


def _expert_params(arch) -> int:
    return 3 * arch.d_model * arch.moe.d_ff_expert


def reach(arch, rows: int) -> float:
    """Expected share of the held experts that ``rows`` tokens reach."""
    m = arch.moe
    return 1.0 - (1.0 - m.top_k / m.n_routed) ** rows


def layer_matmul_params(arch) -> int:
    """Weights one token's products read in one layer, on average:
    attention, the router and the ``k * held / E`` held experts it is
    routed to."""
    m = arch.moe
    return (_attn_params(arch) + arch.d_model * m.n_routed
            + _expert_params(arch) * m.top_k * m.n_held // m.n_routed)


def param_count(arch) -> int:
    m, D = arch.moe, arch.d_model
    layer = (_attn_params(arch) + 2 * D + D * m.n_routed
             + m.n_held * _expert_params(arch))
    if arch.qk_norm:
        layer += 2 * arch.head_dim_
    return arch.vocab * D * (1 if arch.tie_embeddings else 2) + D + arch.n_layers * layer


def kv_token_bytes(arch, dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    return arch.n_layers * 2 * arch.n_kv_heads * arch.head_dim_ * dtype_bytes


def expert_call_bytes(arch, rows: int, dtype_bytes: int = 2) -> float:
    """HBM bytes one layer's held experts need for ``rows`` tokens: the
    weights of the experts the rows reach, and the rows routed to held
    experts read and their outputs written."""
    m = arch.moe
    routed = rows * m.top_k * m.n_held / m.n_routed
    return (m.n_held * reach(arch, rows) * _expert_params(arch)
            + 2 * routed * arch.d_model) * dtype_bytes


def decode_flops(arch, kv_lens: Sequence[int]) -> float:
    """Model FLOPs of one decode step over rows whose caches hold
    ``kv_lens`` tokens before the step: the products with the weights
    each token reads (held experts: ``k * held / E`` a token) and the
    output head, and attention over kv_len + 1 positions."""
    rows = len(kv_lens)
    dense = 2.0 * (arch.n_layers * layer_matmul_params(arch)
                   + arch.d_model * arch.vocab) * rows
    attn = 4.0 * arch.n_layers * arch.n_heads * arch.head_dim_ * float(
        sum(int(n) + 1 for n in kv_lens))
    return dense + attn


def decode_bytes(arch, kv_lens: Sequence[int], dtype_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight but the experts
    once (the embedding table only for the rows' tokens, unless it is
    also the output head), the held experts as far as the rows reach
    them, the K/V of each row's ``kv_len`` tokens, and the K/V it writes."""
    m, D, V = arch.moe, arch.d_model, arch.vocab
    rows = len(kv_lens)
    weights = (param_count(arch) - V * D + rows * D
               - arch.n_layers * m.n_held * _expert_params(arch)
               + arch.n_layers * m.n_held * reach(arch, rows) * _expert_params(arch))
    if arch.tie_embeddings:
        weights += V * D                      # the head reads the whole table
    kv = kv_token_bytes(arch, dtype_bytes) * (float(sum(int(n) for n in kv_lens)) + rows)
    return weights * dtype_bytes + kv
