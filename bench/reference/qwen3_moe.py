"""Plain reference of one expert-parallel device's share of the
Qwen3-MoE decoder, in float32.

The Qwen reference's attention block (``reference/qwen.py``: per-head
q/k RMSNorm before rotate-half RoPE, over the whole causal sequence),
then in every layer a softmax router over all ``routed_experts``
experts, the top-k gates renormalised over the k, and the SwiGLU of
each expert this device holds computed for every token and weighted by
its gate (zero where the token was not routed to it). Experts held
elsewhere add nothing, as on the device. Every matrix product runs at
``Precision.HIGHEST``; the experts run one at a time, so one expert's
float32 weights sit beside the served ones. No cache, no paging, no
batching of sessions.

``quant`` gives the control, as in ``reference/qwen.py``: every matrix
product of attention, the experts and the output head rounded to int8
or fp8 (e4m3), weights per output channel and inputs per token. The
router stays in float32, so the control routes as the reference does.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.qwen import HI, _round, attention, mm, rms, swiglu


def _held_experts(cfg: Dict, quant: Optional[str], h: jnp.ndarray, m: Dict) -> jnp.ndarray:
    T = h.shape[0]
    probs = jax.nn.softmax(jnp.einsum("td,de->te", h, m["router"].astype(jnp.float32),
                                      precision=HI), -1)
    gates, idx = lax.top_k(probs, cfg["top_k"])
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    dense = jnp.zeros(probs.shape, jnp.float32).at[jnp.arange(T)[:, None], idx].set(gates)
    first = cfg["held_first"]
    held = dense[:, first:first + m["w_gate"].shape[0]]          # (T, held)

    def one(acc, xs):
        g, wg, wu, wd = xs
        y = swiglu(quant, h, {"w_gate": wg, "w_up": wu, "w_down": wd})
        return acc + g[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (held.T, m["w_gate"], m["w_up"], m["w_down"]))
    return out


def _layer(cfg, quant, x, p):
    x = attention(cfg, quant, x, p["ln1"], p["attn"])
    return x + _held_experts(cfg, quant, rms(x, p["ln2"], cfg["eps"]), p["moe"]), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_at(cfg_key, quant, params: Dict, tokens: jnp.ndarray,
               positions: jnp.ndarray) -> jnp.ndarray:
    cfg = dict(cfg_key)
    emb = params["embed"]
    x = emb[tokens].astype(jnp.float32)
    if quant:
        x = _round(x, -1, quant)
    x, _ = lax.scan(functools.partial(_layer, cfg, quant), x, params["layers"])
    h = rms(x[positions], params["final_norm"], cfg["eps"])
    return mm(h, emb.T if cfg["tied"] else params["lm_head"], quant)


def logits_at(config: Dict, params: Dict, tokens, positions,
              quant: Optional[str] = None):
    """float32 logits (len(positions), vocab) of one sequence ``tokens``
    at ``positions`` (each position's logits predict the next token).
    ``config`` is the benchmark's configuration file (published keys)."""
    arch = config["architecture"]
    cfg_key = tuple(sorted(dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        top_k=int(config["num_experts_per_tok"]),
        held_first=int(config["deployment"]["held_experts_first"]),
        tied=bool(config["tie_word_embeddings"])).items()))
    return _logits_at(cfg_key, quant, params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32))
