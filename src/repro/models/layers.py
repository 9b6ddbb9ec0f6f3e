"""Core transformer layers: RMSNorm, RoPE / M-RoPE, GQA attention with
chunked (flash-semantics) computation, SwiGLU MLP.

Attention never materializes the full S x S score matrix: an outer
``lax.scan`` over query chunks carries nothing, and an inner scan over KV
chunks carries running (max, denominator, accumulator) -- the standard
online-softmax formulation, which is what makes the 32k prefill and 4k x
256 training shapes fit per-device HBM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import shard_ctx

from .config import ArchConfig

NEG_INF = -1e30


# --------------------------------------------------------------------- norm
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------- rope
def rope_angles(positions: jnp.ndarray, dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (..., S) int -> cos/sin of shape (..., S, dim//2)."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) or (S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def mrope_cos_sin(pos_ids: jnp.ndarray, head_dim: int, theta: float,
                  sections: Tuple[int, int, int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """M-RoPE (qwen2-vl): pos_ids (3, B, S) for (t, h, w) axes.

    Each rotary pair belongs to one of the three sections; its angle uses
    that axis's position id. Returns cos/sin (B, S, head_dim//2).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # section id per rotary pair: [0]*s0 + [1]*s1 + [2]*s2
    sec_id = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)])
    # pick the position for each pair from the matching (t/h/w) axis:
    # (half, B, S) -> (B, S, half)
    pos = pos_ids.astype(jnp.float32)[sec_id, :, :].transpose(1, 2, 0)
    # angle = pos * freq per pair
    ang = pos * freqs[None, None, :]
    return jnp.cos(ang), jnp.sin(ang)


# ----------------------------------------------------- chunked attention
#
# Flash-semantics attention with a CUSTOM VJP. Plain autodiff through the
# online-softmax scans makes XLA save the per-tile probability tensors for
# the backward pass -- O(S^2) memory again, measured at ~15 GB/device/layer
# on the train_4k cells. The custom backward recomputes each tile's
# probabilities from the saved logsumexp (the FlashAttention-2 recipe),
# so both passes stay O(S * chunk) in memory.

class _FlashCfg(NamedTuple):
    causal: bool
    cq: int
    ckv: int
    scale: float
    q_offset: int
    nq: int
    nkv: int
    skv: int                     # valid kv length (for padding mask)


def _tile_bias(cfg: _FlashCfg, qi, kj) -> jnp.ndarray:
    """2-D (cq, ckv) additive bias for tile (qi, kj): padding + causality.

    Kept 2-D (no B/H dims) so XLA cannot hoist a 5-D mask buffer out of
    the chunk loops (measured 37 GB/device before this change).
    """
    kpos = kj * cfg.ckv + jnp.arange(cfg.ckv)
    bias = jnp.where(kpos < cfg.skv, 0.0, NEG_INF)[None, :]
    if cfg.causal:
        qpos = cfg.q_offset + qi * cfg.cq + jnp.arange(cfg.cq)
        bias = bias + jnp.where(qpos[:, None] >= kpos[None, :], 0.0, NEG_INF)
    return bias.astype(jnp.float32)


def _flash_fwd_pass(cfg: _FlashCfg, qs, ks, vs):
    """qs: (nq, B, cq, H, hd) pre-scaled; ks/vs: (nkv, B, ckv, H, hd).

    Returns out (nq, B, cq, H, hd) and lse (nq, B, H, cq).
    """
    nq, B, cq, H, hd = qs.shape

    def q_step(_, qi_q):
        qi, qc = qi_q
        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        o0 = jnp.zeros((B, cq, H, hd), jnp.float32)

        def kv_step(carry, kj_kv):
            m, l, o = carry
            kj, kc, vc = kj_kv
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32)
            s = s + _tile_bias(cfg, qi, kj)[None, None]
            mc = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, mc)
            p = jnp.exp(s - m_new[..., None])
            a = jnp.exp(m - m_new)
            l_new = l * a + jnp.sum(p, axis=-1)
            oc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc)
            o_new = o * a.transpose(0, 2, 1)[..., None] + oc.astype(jnp.float32)
            return (m_new, l_new, o_new), None

        (m, l, o), _ = lax.scan(kv_step, (m0, l0, o0),
                                (jnp.arange(cfg.nkv), ks, vs))
        l = jnp.maximum(l, 1e-30)
        out = (o / l.transpose(0, 2, 1)[..., None]).astype(vs.dtype)
        lse = m + jnp.log(l)
        return None, (out, lse)

    _, (outs, lses) = lax.scan(q_step, None, (jnp.arange(nq), qs))
    return outs, lses


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _FlashCfg, qs, ks, vs):
    out, _ = _flash_fwd_pass(cfg, qs, ks, vs)
    return out


def _flash_fwd(cfg: _FlashCfg, qs, ks, vs):
    out, lse = _flash_fwd_pass(cfg, qs, ks, vs)
    return out, (qs, ks, vs, out, lse)


def _flash_bwd(cfg: _FlashCfg, res, do):
    qs, ks, vs, out, lse = res
    nq, B, cq, H, hd = qs.shape
    # delta_i = sum_d do_id * o_id  -> (nq, B, H, cq)
    delta = jnp.einsum("nbqhd,nbqhd->nbhq", do.astype(jnp.float32),
                       out.astype(jnp.float32))

    def p_tile(qi, kj, qc, kc, lse_c):
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32)
        s = s + _tile_bias(cfg, qi, kj)[None, None]
        return jnp.exp(s - lse_c[..., None])          # (B,H,cq,ckv)

    # ---- dq: outer scan over q chunks, inner over kv chunks
    def dq_step(_, xs):
        qi, qc, do_c, lse_c, delta_c = xs

        def kv_step(dq_acc, kj_kv):
            kj, kc, vc = kj_kv
            p = p_tile(qi, kj, qc, kc, lse_c)
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_c.astype(jnp.float32),
                            vc.astype(jnp.float32))
            ds = p * (dp - delta_c[..., None])
            return dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                       kc.astype(jnp.float32)), None

        dq0 = jnp.zeros((B, cq, H, hd), jnp.float32)
        dq_c, _ = lax.scan(kv_step, dq0, (jnp.arange(cfg.nkv), ks, vs))
        return None, dq_c

    _, dqs = lax.scan(dq_step, None,
                      (jnp.arange(nq), qs, do, lse, delta))

    # ---- dk/dv: outer scan over kv chunks, inner over q chunks
    ckv = ks.shape[2]

    def dkv_step(_, xs):
        kj, kc, vc = xs

        def q_step(acc, qx):
            dk_acc, dv_acc = acc
            qi, qc, do_c, lse_c, delta_c = qx
            p = p_tile(qi, kj, qc, kc, lse_c)
            dv_acc = dv_acc + jnp.einsum("bhqk,bqhd->bkhd", p,
                                         do_c.astype(jnp.float32))
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_c.astype(jnp.float32),
                            vc.astype(jnp.float32))
            ds = p * (dp - delta_c[..., None])
            dk_acc = dk_acc + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                         qc.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, ckv, H, hd), jnp.float32)
        (dk_c, dv_c), _ = lax.scan(q_step, (z, z),
                                   (jnp.arange(nq), qs, do, lse, delta))
        return None, (dk_c, dv_c)

    _, (dks, dvs) = lax.scan(dkv_step, None, (jnp.arange(cfg.nkv), ks, vs))
    return dqs.astype(qs.dtype), dks.astype(ks.dtype), dvs.astype(vs.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      *, causal: bool, chunk_q: int, chunk_kv: int,
                      scale: Optional[float] = None,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention with flash custom VJP.

    q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd) with Hq % Hkv == 0 (GQA:
    K/V are repeated to Hq -- the repeat's own VJP reduces the grads back
    over the head groups). Returns (B, Sq, Hq, hd).
    ``q_offset``: absolute position of q[0] (decode: Skv - 1).
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hkv != Hq:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else hd ** -0.5
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    cq = min(chunk_q, Sq)
    ckv = min(chunk_kv, Skv)
    pad_q = (-Sq) % cq
    pad_kv = (-Skv) % ckv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq = (Sq + pad_q) // cq
    nkv = (Skv + pad_kv) // ckv

    qs = q.reshape(B, nq, cq, Hq, hd).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(B, nkv, ckv, Hq, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nkv, ckv, Hq, hd).transpose(1, 0, 2, 3, 4)

    cfg = _FlashCfg(causal=causal, cq=cq, ckv=ckv, scale=scale,
                    q_offset=q_offset, nq=nq, nkv=nkv, skv=Skv)
    outs = _flash(cfg, qs, ks, vs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * cq, Hq, hd)
    return out[:, :Sq]


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode attention.

    q: (B, 1, Hq, hd); k/v: (B, S, Hkv, hd); kv_len: (B,) valid lengths.
    """
    B, _, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    scale = scale if scale is not None else hd ** -0.5
    g = Hq // Hkv
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, Hkv, g, hd)
    # keep k/v in their storage dtype: XLA can hoist an fp32 upcast of them
    # out of the layer scan as a convert of the whole pool, which then
    # moves twice the pool's bytes every step
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                   preferred_element_type=jnp.float32)
    if kv_len is not None:
        mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------- mlp
def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    g = shard_ctx.ffn_hidden(jnp.einsum("...d,df->...f", x, w_gate))
    u = shard_ctx.ffn_hidden(jnp.einsum("...d,df->...f", x, w_up))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


# ------------------------------------------------------------ attention op
def attention_block(x: jnp.ndarray, p: dict, cfg: ArchConfig,
                    cos: jnp.ndarray, sin: jnp.ndarray,
                    *, causal: bool) -> jnp.ndarray:
    """Full attention sub-layer (projections + rope + chunked attn)."""
    B, S, D = x.shape
    hd = cfg.head_dim_
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = shard_ctx.heads(q.reshape(B, S, cfg.n_heads, hd))
    k = shard_ctx.heads(k.reshape(B, S, cfg.n_kv_heads, hd), kv=True)
    v = shard_ctx.heads(v.reshape(B, S, cfg.n_kv_heads, hd), kv=True)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = chunked_attention(q, k, v, causal=causal,
                          chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv)
    o = o.reshape(B, S, cfg.n_heads * hd)
    return shard_ctx.act(jnp.einsum("bse,ed->bsd", o, p["wo"]))
