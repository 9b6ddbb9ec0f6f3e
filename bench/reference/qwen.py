"""Plain reference of the Qwen2 / Qwen3 decoder, in float32.

The published architecture, written straight: pre-norm RMSNorm blocks,
grouped-query attention with rotate-half RoPE over full causal
sequences, optional q/k/v bias (Qwen2) and per-head q/k RMSNorm (Qwen3),
SwiGLU MLP, optionally tied embeddings. Every matrix product runs at
``Precision.HIGHEST``. No cache, no paging, no batching of sessions.

``quant`` gives the control: every weight matrix and its input
activations rounded per output channel / per token to int8 or fp8
(e4m3) before the product -- what serving one precision step below
bfloat16 computes.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _round(x: jnp.ndarray, axis: int, quant: str) -> jnp.ndarray:
    """Fake-quantize ``x`` with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


def mm(x: jnp.ndarray, w: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    """x (..., i) @ w (i, o) in float32."""
    w = w.astype(jnp.float32)
    if quant:
        x = _round(x, -1, quant)
        w = _round(w, 0, quant)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rms(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (T, heads, hd), rotate-half RoPE at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg: Dict, quant: Optional[str], x: jnp.ndarray, ln: jnp.ndarray,
              a: Dict) -> jnp.ndarray:
    """x (T, D) plus its pre-norm attention block over the whole causal
    sequence; ``cfg`` holds ``heads``, ``kv_heads``, ``head_dim``, ``eps``,
    ``theta``, ``qkv_bias`` and ``qk_norm``."""
    T = x.shape[0]
    H, KV, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    eps = cfg["eps"]
    h = rms(x, ln, eps)
    q, k, v = mm(h, a["wq"], quant), mm(h, a["wk"], quant), mm(h, a["wv"], quant)
    if cfg["qkv_bias"]:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q, k, v = q.reshape(T, H, hd), k.reshape(T, KV, hd), v.reshape(T, KV, hd)
    if cfg["qk_norm"]:
        q, k = rms(q, a["q_norm"], eps), rms(k, a["k_norm"], eps)
    q, k = _rope(q, cfg["theta"]), _rope(k, cfg["theta"])
    g = H // KV
    qg = q.reshape(T, KV, g, hd)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v, precision=HI)
    return x + mm(o.reshape(T, H * hd), a["wo"], quant)


def swiglu(quant: Optional[str], h: jnp.ndarray, m: Dict) -> jnp.ndarray:
    """The SwiGLU MLP ``m`` (w_gate, w_up, w_down) of h (T, D)."""
    u = jax.nn.silu(mm(h, m["w_gate"], quant)) * mm(h, m["w_up"], quant)
    return mm(u, m["w_down"], quant)


def _layer(cfg: Dict, quant: Optional[str], x: jnp.ndarray, p: Dict):
    x = attention(cfg, quant, x, p["ln1"], p["attn"])
    return x + swiglu(quant, rms(x, p["ln2"], cfg["eps"]), p["mlp"]), None


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
    head = emb.T if cfg["tied"] else params["lm_head"]
    return mm(h, head, quant)


def logits_at(config: Dict, params: Dict, tokens, positions,
              quant: Optional[str] = None):
    """float32 logits (len(positions), vocab) of one sequence ``tokens``
    at ``positions`` (each position's logits predict the next token).
    ``config`` is the benchmark's configuration file (published keys)."""
    heads = int(config["num_attention_heads"])
    arch = config["architecture"]
    cfg_key = tuple(sorted(dict(
        heads=heads, kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or config["hidden_size"] // heads),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tied=bool(config["tie_word_embeddings"])).items()))
    return _logits_at(cfg_key, quant, params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32))
