"""Plain float32 reference of the test MoE family: the Qwen reference's
attention and SwiGLU blocks, a leading dense layer, then layers whose
MLP is a softmax router's top-k routed experts (gates renormalised over
the k) plus the shared experts, every expert computed for every token
and weighted by its gate (zero where not routed)."""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.qwen import HI, _round, attention, mm, rms, swiglu


def _moe(cfg: Dict, quant: Optional[str], h: jnp.ndarray, m: Dict) -> jnp.ndarray:
    probs = jax.nn.softmax(jnp.einsum("td,de->te", h, m["router"].astype(jnp.float32),
                                      precision=HI), -1)
    gates, idx = lax.top_k(probs, cfg["top_k"])
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    T, E = probs.shape
    dense_gates = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(gates)
    experts = jax.vmap(lambda g, u, d: swiglu(quant, h, {"w_gate": g, "w_up": u,
                                                         "w_down": d}))(
        m["w_gate"], m["w_up"], m["w_down"])                  # (E, T, D)
    shared = swiglu(quant, h, {"w_gate": m["shared_gate"], "w_up": m["shared_up"],
                               "w_down": m["shared_down"]})
    return jnp.einsum("te,etd->td", dense_gates, experts, precision=HI) + shared


def _layer(cfg, quant, x, p):
    x = attention(cfg, quant, x, p["ln1"], p["attn"])
    return x + _moe(cfg, quant, rms(x, p["ln2"], cfg["eps"]), p["moe"]), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_at(cfg_key, quant, params, tokens, positions):
    cfg = dict(cfg_key)
    emb = params["embed"]
    x = emb[tokens].astype(jnp.float32)
    if quant:
        x = _round(x, -1, quant)
    if "layer0" in params:
        p0 = params["layer0"]
        x = attention(cfg, quant, x, p0["ln1"], p0["attn"])
        x = x + swiglu(quant, rms(x, p0["ln2"], cfg["eps"]), p0["mlp"])
    x, _ = lax.scan(functools.partial(_layer, cfg, quant), x, params["layers"])
    h = rms(x[positions], params["final_norm"], cfg["eps"])
    return mm(h, emb.T if cfg["tied"] else params["lm_head"], quant)


def logits_at(config: Dict, params: Dict, tokens, positions,
              quant: Optional[str] = None):
    cfg_key = tuple(sorted(dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        qkv_bias=False, qk_norm=False, top_k=int(config["num_experts_per_tok"]),
        tied=bool(config["tie_word_embeddings"])).items()))
    return _logits_at(cfg_key, quant, params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32))
