"""Model assembly: parameter init + train/prefill/decode for all families.

Layer stacks are ``lax.scan``-ed over stacked parameters (leading layer
dim) with ``jax.checkpoint`` on the body -- one compiled body per arch
regardless of depth, activation remat by default. The jamba hybrid scans
over groups of ``hybrid_group`` layers (7 mamba + 1 attention, FFN
alternating dense/MoE), keeping heterogeneity inside the scanned body.

Decode uses a paged KV cache: per attention layer a block pool
``(n_blocks, block_tokens, 2 * kv_heads * head_dim)`` addressed through a
``(B, max_blocks)`` block table -- the device-side analogue of Taiji's
block-table (EPT) indirection, and the structure the elastic KV manager
swaps at MS granularity. A token's K/V is one flat row in the order
``(2, kv_heads, head_dim)``, so the pool's minor dimension is as wide
at every KV geometry and no step relays the pool out.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import shard_ctx

from .config import ArchConfig
from .layers import (apply_rope, attention_block,
                     decode_attention, mrope_cos_sin, rms_norm, rope_angles,
                     swiglu)
from .moe import moe_decode, moe_ffn
from .ssm import mamba_block, mamba_decode_step

Params = Dict[str, Any]


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ============================================================== param init
def _init_attn(key, cfg: ArchConfig, n: int = 1) -> Params:
    """Attention params, optionally stacked over ``n`` layers."""
    D, hd = cfg.d_model, cfg.head_dim_
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg.param_dtype)
    shape = lambda *s: (n, *s) if n > 1 else s
    std = 0.02
    p = {
        "wq": jax.random.normal(ks[0], shape(D, H * hd), dt) * std,
        "wk": jax.random.normal(ks[1], shape(D, KV * hd), dt) * std,
        "wv": jax.random.normal(ks[2], shape(D, KV * hd), dt) * std,
        "wo": jax.random.normal(ks[3], shape(H * hd, D), dt) * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(shape(H * hd), dt)
        p["bk"] = jnp.zeros(shape(KV * hd), dt)
        p["bv"] = jnp.zeros(shape(KV * hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(shape(hd), dt)
        p["k_norm"] = jnp.ones(shape(hd), dt)
    return p


def _init_mlp(key, cfg: ArchConfig, d_ff: int, n: int = 1) -> Params:
    D = cfg.d_model
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    shape = lambda *s: (n, *s) if n > 1 else s
    std = 0.02
    return {
        "w_gate": jax.random.normal(ks[0], shape(D, d_ff), dt) * std,
        "w_up": jax.random.normal(ks[1], shape(D, d_ff), dt) * std,
        "w_down": jax.random.normal(ks[2], shape(d_ff, D), dt) * (std / math.sqrt(2 * cfg.n_layers)),
    }


def _init_moe(key, cfg: ArchConfig, n: int = 1) -> Params:
    """The router over all ``n_routed`` experts and the weights of the
    ``n_held`` experts this device holds."""
    m = cfg.moe
    D, E, Eh, F = cfg.d_model, m.n_routed, m.n_held, m.d_ff_expert
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    shape = lambda *s: (n, *s) if n > 1 else s
    std = 0.02
    p = {
        "router": jax.random.normal(ks[0], shape(D, E), dt) * std,
        "w_gate": jax.random.normal(ks[1], shape(Eh, D, F), dt) * std,
        "w_up": jax.random.normal(ks[2], shape(Eh, D, F), dt) * std,
        "w_down": jax.random.normal(ks[3], shape(Eh, F, D), dt) * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if m.n_shared:
        Fs = m.n_shared * F
        p["shared_gate"] = jax.random.normal(ks[4], shape(D, Fs), dt) * std
        p["shared_up"] = jax.random.normal(ks[5], shape(D, Fs), dt) * std
        p["shared_down"] = jax.random.normal(ks[6], shape(Fs, D), dt) * (std / math.sqrt(2 * cfg.n_layers))
    return p


def _init_mamba(key, cfg: ArchConfig, n: int = 1) -> Params:
    mc = cfg.mamba
    D, DI, DS = cfg.d_model, cfg.d_inner, mc.d_state
    dtr = cfg.dt_rank_
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    shape = lambda *s: (n, *s) if n > 1 else s
    std = 0.02
    # S4-style A init: -(1..d_state) per channel
    A = jnp.tile(jnp.arange(1, DS + 1, dtype=jnp.float32)[None, :], (DI, 1))
    A_log = jnp.log(A).astype(dt)
    if n > 1:
        A_log = jnp.tile(A_log[None], (n, 1, 1))
    return {
        "in_proj": jax.random.normal(ks[0], shape(D, 2 * DI), dt) * std,
        "conv_w": jax.random.normal(ks[1], shape(mc.d_conv, DI), dt) * std,
        "conv_b": jnp.zeros(shape(DI), dt),
        "x_proj": jax.random.normal(ks[2], shape(DI, dtr + 2 * DS), dt) * std,
        "dt_proj": jax.random.normal(ks[3], shape(dtr, DI), dt) * (dtr ** -0.5),
        "dt_bias": jnp.full(shape(DI), math.log(math.e - 1), dt),  # softplus^-1(1)
        "A_log": A_log,
        "D": jnp.ones(shape(DI), dt),
        "out_proj": jax.random.normal(ks[4], shape(DI, D), dt) * (std / math.sqrt(2 * cfg.n_layers)),
    }


def init_params(rng: jax.Array, cfg: ArchConfig) -> Params:
    cfg.validate()
    dt = _dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 16)
    D, V = cfg.d_model, cfg.vocab
    params: Params = {
        "embed": jax.random.normal(keys[0], (V, D), dt) * 0.02,
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (D, V), dt) * 0.02
    if cfg.frontend_dim:
        params["frontend_proj"] = jax.random.normal(
            keys[2], (cfg.frontend_dim, D), dt) * 0.02

    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.hybrid_group
        g = cfg.hybrid_group
        n_mamba = g - 1                # mamba layers per group
        n_moe = g // 2                 # MoE FFNs per group (every other)
        n_mlp = g - n_moe              # dense FFNs per group
        sub = jax.random.split(keys[3], 8)
        layers = {
            "ln_mix": jnp.ones((G, g, D), dt),
            "ln_ffn": jnp.ones((G, g, D), dt),
            "attn": _stack_over_groups(lambda k: _init_attn(k, cfg), sub[1], G),
            "mamba": _stack_over_groups(
                lambda k: _init_mamba(k, cfg, n=n_mamba), sub[2], G),
            "moe": _stack_over_groups(
                lambda k: _init_moe(k, cfg, n=n_moe), sub[3], G),
            "mlp": _stack_over_groups(
                lambda k: _init_mlp(k, cfg, cfg.d_ff, n=n_mlp), sub[4], G),
        }
        params["layers"] = layers
        return params

    if cfg.family == "ssm":
        L = cfg.n_layers
        params["layers"] = {
            "ln1": jnp.ones((L, D), dt),
            "mamba": _init_mamba(keys[3], cfg, n=L),
        }
        return params

    # dense / moe / audio / vlm: homogeneous decoder or encoder stack
    m = cfg.moe
    first_dense = m is not None and m.first > 0
    L = cfg.n_layers - (1 if first_dense else 0)
    layers: Params = {
        "ln1": jnp.ones((L, D), dt),
        "ln2": jnp.ones((L, D), dt),
        "attn": _init_attn(keys[3], cfg, n=L),
    }
    if m is not None:
        layers["moe"] = _init_moe(keys[4], cfg, n=L)
    else:
        layers["mlp"] = _init_mlp(keys[4], cfg, cfg.d_ff, n=L)
    params["layers"] = layers
    if first_dense:
        params["layer0"] = {
            "ln1": jnp.ones((D,), dt),
            "ln2": jnp.ones((D,), dt),
            "attn": _init_attn(keys[5], cfg),
            "mlp": _init_mlp(keys[6], cfg, cfg.d_ff),
        }
    return params


def _stack_over_groups(fn, key, G: int) -> Params:
    """Initialize ``fn`` per group and stack leaves -> leading dim G."""
    trees = [fn(k) for k in jax.random.split(key, G)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def param_shapes(cfg: ArchConfig) -> Params:
    """Shape/dtype tree without allocating (dry-run input)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ================================================================= forward
def _ffn_dispatch(x, layer_p, cfg: ArchConfig, is_moe: bool):
    if is_moe:
        return moe_ffn(x, layer_p, cfg)
    return swiglu(x, layer_p["w_gate"], layer_p["w_up"], layer_p["w_down"]), 0.0


def _cast(p, dtype):
    return jax.tree.map(lambda w: w.astype(dtype), p)


def _dense_layer_body(cfg: ArchConfig, cos, sin, causal: bool):
    """Per-layer body for the homogeneous stacks (dense/moe/audio/vlm)."""
    cdt = _dtype(cfg.compute_dtype)
    has_moe = cfg.moe is not None

    def body(carry, layer_p):
        x, aux = carry
        layer_p = _cast(layer_p, cdt)
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        h = attention_block(h, layer_p["attn"], cfg, cos, sin, causal=causal)
        x = x + h
        h = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        if has_moe:
            h, a = moe_ffn(h, layer_p["moe"], cfg)
        else:
            h, a = _ffn_dispatch(h, layer_p["mlp"], cfg, False)
        return (shard_ctx.act(x + h), aux + a), None

    return body


def _hybrid_group_body(cfg: ArchConfig, cos, sin):
    """jamba: one scanned step = hybrid_group layers."""
    cdt = _dtype(cfg.compute_dtype)
    g = cfg.hybrid_group

    def body(carry, group_p):
        x, aux = carry
        group_p = _cast(group_p, cdt)
        mi = 0
        for j in range(g):
            h = rms_norm(x, group_p["ln_mix"][j], cfg.norm_eps)
            if j == cfg.attn_index:
                h = attention_block(h, group_p["attn"], cfg, cos, sin,
                                    causal=True)
            else:
                mp = jax.tree.map(lambda w: w[mi], group_p["mamba"])
                h = mamba_block(h, mp, cfg)
                mi += 1
            x = x + h
            h = rms_norm(x, group_p["ln_ffn"][j], cfg.norm_eps)
            if j % 2 == 1:                      # MoE every other layer
                mo = jax.tree.map(lambda w: w[j // 2], group_p["moe"])
                h, a = moe_ffn(h, mo, cfg)
            else:
                ml = jax.tree.map(lambda w: w[j // 2], group_p["mlp"])
                h, a = _ffn_dispatch(h, ml, cfg, False)
            x = shard_ctx.act(x + h)
            aux = aux + a
        return (x, aux), None

    return body


def _ssm_layer_body(cfg: ArchConfig):
    cdt = _dtype(cfg.compute_dtype)

    def body(carry, layer_p):
        x, aux = carry
        layer_p = _cast(layer_p, cdt)
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        h = mamba_block(h, layer_p["mamba"], cfg)
        return (shard_ctx.act(x + h), aux), None

    return body


def _embed_inputs(params: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    cdt = _dtype(cfg.compute_dtype)
    if cfg.family == "audio":
        x = batch["features"].astype(cdt) @ params["frontend_proj"].astype(cdt)
        return x
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(cdt)
    if cfg.family == "vlm":
        nv = batch["vision_embeds"].shape[1]
        x = lax.dynamic_update_slice(
            x, batch["vision_embeds"].astype(cdt), (0, 0, 0))
        del nv
    return x


def _positions_cos_sin(cfg: ArchConfig, batch: Dict[str, jnp.ndarray], S: int):
    hd = cfg.head_dim_
    if cfg.mrope_sections is not None:
        pos_ids = batch["mrope_pos"]                 # (3, B, S)
        return mrope_cos_sin(pos_ids, hd, cfg.rope_theta, cfg.mrope_sections)
    pos = jnp.arange(S)
    return rope_angles(pos, hd, cfg.rope_theta)


def forward(params: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            *, remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward -> (hidden (B,S,D) fp-compute, aux_loss)."""
    x = shard_ctx.act(_embed_inputs(params, cfg, batch))
    B, S, D = x.shape
    aux = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        body = _ssm_layer_body(cfg)
    elif cfg.family == "hybrid":
        cos, sin = _positions_cos_sin(cfg, batch, S)
        body = _hybrid_group_body(cfg, cos, sin)
    else:
        cos, sin = _positions_cos_sin(cfg, batch, S)
        body = _dense_layer_body(cfg, cos, sin, causal=cfg.causal)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    m = cfg.moe
    if m is not None and m.first > 0 and "layer0" in params:
        p0 = _cast(params["layer0"], _dtype(cfg.compute_dtype))
        h = rms_norm(x, p0["ln1"], cfg.norm_eps)
        h = attention_block(h, p0["attn"], cfg, cos, sin, causal=cfg.causal)
        x = x + h
        h = rms_norm(x, p0["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p0["mlp"]["w_gate"], p0["mlp"]["w_up"],
                       p0["mlp"]["w_down"])

    (x, aux), _ = lax.scan(body, (x, aux), params["layers"])
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x, aux


def logits_from_hidden(params: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return shard_ctx.logits(jnp.einsum("...d,dv->...v", x, head.astype(x.dtype)))


def loss_fn(params: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token (decoder) or frame-label (encoder) cross entropy."""
    hidden, aux = forward(params, cfg, batch)
    logits = logits_from_hidden(params, cfg, hidden)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ================================================================== decode
@dataclasses.dataclass
class CacheSpec:
    """Geometry of the paged decode cache for one arch/shape."""
    batch: int
    max_seq: int
    n_attn_layers: int
    n_mamba_layers: int

    def n_blocks(self, cfg: ArchConfig) -> int:
        return self.batch * (self.max_seq // cfg.kv_block_tokens)

    def max_blocks_per_seq(self, cfg: ArchConfig) -> int:
        return self.max_seq // cfg.kv_block_tokens


def attn_layer_count(cfg: ArchConfig) -> int:
    return sum(cfg.is_attn_layer(l) for l in range(cfg.n_layers)
               ) if cfg.n_heads else 0


def mamba_layer_count(cfg: ArchConfig) -> int:
    if cfg.mamba is None:
        return 0
    return sum(not cfg.is_attn_layer(l) for l in range(cfg.n_layers))


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Allocate an empty paged decode cache."""
    spec = CacheSpec(batch, max_seq, attn_layer_count(cfg),
                     mamba_layer_count(cfg))
    bt = cfg.kv_block_tokens
    cache: Dict[str, jnp.ndarray] = {
        "kv_len": jnp.zeros((batch,), jnp.int32),
    }
    if spec.n_attn_layers:
        nb = spec.n_blocks(cfg)
        mbs = spec.max_blocks_per_seq(cfg)
        if cfg.kv_pool_layout == "per_seq":
            # pool factored per sequence: the block table indexes within a
            # sequence's own partition, so gathers stay batch-aligned and
            # shard-local (per-host pools on TPU serving)
            cache["kv_pool"] = jnp.zeros(
                (spec.n_attn_layers, batch, mbs, bt, 2, cfg.n_kv_heads,
                 cfg.head_dim_), dtype)
            cache["block_table"] = jnp.tile(
                jnp.arange(mbs, dtype=jnp.int32)[None, :], (batch, 1))
        else:
            cache["kv_pool"] = jnp.zeros(
                (spec.n_attn_layers, nb, bt, 2 * cfg.n_kv_heads * cfg.head_dim_),
                dtype)
            # sequence i owns pool rows [i*mbs, (i+1)*mbs)
            cache["block_table"] = (jnp.arange(batch)[:, None] * mbs
                                    + jnp.arange(mbs)[None, :]).astype(jnp.int32)
    if spec.n_mamba_layers:
        mc = cfg.mamba
        cache["conv_state"] = jnp.zeros(
            (spec.n_mamba_layers, batch, mc.d_conv - 1, cfg.d_inner), jnp.float32)
        cache["ssm_state"] = jnp.zeros(
            (spec.n_mamba_layers, batch, cfg.d_inner, mc.d_state), jnp.float32)
    return cache


def _paged_kv_read(pool: jnp.ndarray, layer, block_table: jnp.ndarray,
                   pos: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
    """One layer's K/V for the batch, with this token's K/V at ``pos``.

    pool: (L, n_blocks, bt, 2 * KV * hd) [global layout] or
    (L, B, mbs, bt, 2, KV, hd) [per_seq layout], read only; layer: scalar;
    block_table: (B, mbs); pos: (B,) absolute positions; kv: (B, 2, KV, hd)
    in the pool's dtype. Returns the sequence-major view
    (B, mbs * bt, 2, KV, hd): what the pool holds for these rows once
    :func:`_paged_kv_commit` has written ``kv``.
    """
    if pool.ndim == 7:                       # per_seq: batch-aligned gather
        B = block_table.shape[0]
        blocks = pool[layer, jnp.arange(B)[:, None], block_table]
    else:
        blocks = pool[layer, block_table]    # (B, mbs, bt, 2 * KV * hd)
    B, mbs, bt = blocks.shape[:3]
    seq = blocks.reshape(B, mbs * bt, *kv.shape[1:])
    at_pos = jnp.arange(mbs * bt)[None, :] == pos[:, None]
    return jnp.where(at_pos[:, :, None, None, None], kv[:, None], seq)


def _paged_kv_commit(pool: jnp.ndarray, block_table: jnp.ndarray,
                     pos: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
    """Write every layer's new K/V, kv: (L, B, 2, KV, hd), into the pool
    at ``pos``: one scatter, in place where the pool is donated. Each row
    of a batch owns its blocks, so no two updates hit one slot."""
    L, B = kv.shape[:2]
    blk_dims = 4 if pool.ndim == 7 else 2    # (bt, 2, KV, hd) or (bt, 2*KV*hd)
    bt = pool.shape[-blk_dims]
    blk = jnp.take_along_axis(block_table, (pos // bt)[:, None], axis=1)[:, 0]
    layer = jnp.arange(L)[:, None]
    if pool.ndim == 7:                       # per_seq: (layer, row, block, slot)
        idx = (layer, jnp.arange(B)[None], blk[None], (pos % bt)[None])
    else:                                    # global: (layer, block, slot)
        idx = (layer, blk[None], (pos % bt)[None])
        kv = kv.reshape(L, B, -1)
    return pool.at[idx].set(kv, unique_indices=True)


def decode_step(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                cache: Dict[str, jnp.ndarray],
                mrope_pos: Optional[jnp.ndarray] = None,
                input_embeds: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step: tokens (B,) -> (logits (B,V), cache').

    ``input_embeds`` (B, D), if given, overrides the token embedding --
    used when replaying a multimodal prefix (vision patches) through the
    decode path.
    """
    logits, _, new_cache = decode_step_kv(params, cfg, tokens, cache,
                                          mrope_pos, input_embeds)
    return logits, new_cache


def decode_step_kv(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                   cache: Dict[str, jnp.ndarray],
                   mrope_pos: Optional[jnp.ndarray] = None,
                   input_embeds: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                              Dict[str, jnp.ndarray]]:
    """:func:`decode_step` that also returns the K/V it wrote:
    -> (logits (B, V), kv (La, B, 2, KV, hd) or None, cache').

    The pool never passes through the layer scan: each layer gathers its
    rows' blocks from the whole pool, which it only reads, and the scan
    stacks each layer's new K/V (``kv``, in the pool's dtype); one scatter
    after the scan writes them all into the pool.
    """
    cdt = _dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    hd = cfg.head_dim_
    pos = cache["kv_len"]                                    # (B,)

    if input_embeds is not None:
        x = input_embeds.astype(cdt)
    else:
        x = params["embed"][tokens].astype(cdt)              # (B, D)

    # rope angles at the current position
    if cfg.mrope_sections is not None:
        p3 = (mrope_pos if mrope_pos is not None
              else jnp.tile(pos[None, :, None], (3, 1, 1)))  # (3, B, 1)
        cos, sin = mrope_cos_sin(p3, hd, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.n_heads:
        cos, sin = rope_angles(pos[:, None], hd, cfg.rope_theta)  # (B,1,half)
    else:
        cos = sin = None

    pool = cache.get("kv_pool")

    def attn_decode(h2, layer_p, layer):
        q = h2 @ layer_p["wq"]
        k = h2 @ layer_p["wk"]
        v = h2 @ layer_p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + layer_p["bq"], k + layer_p["bk"], v + layer_p["bv"]
        # decode attention is pure-DP over the batch: heads stay replicated
        # per device so the (KV, group) factorization never reshards the
        # batch-local K/V
        q = shard_ctx.act(q.reshape(B, 1, cfg.n_heads, hd))
        k = shard_ctx.act(k.reshape(B, 1, cfg.n_kv_heads, hd))
        v = shard_ctx.act(v.reshape(B, 1, cfg.n_kv_heads, hd))
        if cfg.qk_norm:
            q = rms_norm(q, layer_p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, layer_p["k_norm"], cfg.norm_eps)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        kv = jnp.stack([k[:, 0], v[:, 0]], axis=1).astype(pool.dtype)
        seq = shard_ctx.act(_paged_kv_read(pool, layer, cache["block_table"],
                                           pos, kv))
        o = decode_attention(q, seq[:, :, 0].astype(cdt),
                             seq[:, :, 1].astype(cdt), kv_len=pos + 1)
        o = o.reshape(B, cfg.n_heads * hd)
        return o @ layer_p["wo"], kv

    new_cache = dict(cache)
    kvs = None

    if cfg.family == "hybrid":
        g = cfg.hybrid_group
        G = cfg.n_layers // g

        def group_step(x1, xs):
            group_p, layer, conv_g, ssm_g = xs
            group_p = _cast(group_p, cdt)
            mi = 0
            conv_out, ssm_out = [], []
            for j in range(g):
                h = rms_norm(x1, group_p["ln_mix"][j], cfg.norm_eps)
                if j == cfg.attn_index:
                    h, kv = attn_decode(h, group_p["attn"], layer)
                else:
                    mp = jax.tree.map(lambda w: w[mi], group_p["mamba"])
                    h, cs, ss = mamba_decode_step(
                        h, mp, cfg, conv_g[mi], ssm_g[mi])
                    conv_out.append(cs)
                    ssm_out.append(ss)
                    mi += 1
                x1 = x1 + h
                h = rms_norm(x1, group_p["ln_ffn"][j], cfg.norm_eps)
                if j % 2 == 1:
                    mo = jax.tree.map(lambda w: w[j // 2], group_p["moe"])
                    h = moe_decode(h, mo, cfg)
                else:
                    ml = jax.tree.map(lambda w: w[j // 2], group_p["mlp"])
                    h = swiglu(h, ml["w_gate"], ml["w_up"], ml["w_down"])
                x1 = x1 + h
            return x1, (kv, jnp.stack(conv_out), jnp.stack(ssm_out))

        x, (kvs, convs, ssms) = lax.scan(
            group_step, x,
            (params["layers"], jnp.arange(G),
             cache["conv_state"].reshape(G, g - 1, B, cfg.mamba.d_conv - 1,
                                         cfg.d_inner),
             cache["ssm_state"].reshape(G, g - 1, B, cfg.d_inner,
                                        cfg.mamba.d_state)))
        new_cache["conv_state"] = convs.reshape(cache["conv_state"].shape)
        new_cache["ssm_state"] = ssms.reshape(cache["ssm_state"].shape)

    elif cfg.family == "ssm":
        def layer_step(x1, xs):
            layer_p, conv_s, ssm_s = xs
            layer_p = _cast(layer_p, cdt)
            h = rms_norm(x1, layer_p["ln1"], cfg.norm_eps)
            h, cs, ss = mamba_decode_step(h, layer_p["mamba"], cfg,
                                          conv_s, ssm_s)
            return x1 + h, (cs, ss)

        x, (convs, ssms) = lax.scan(
            layer_step, x,
            (params["layers"], cache["conv_state"], cache["ssm_state"]))
        new_cache["conv_state"] = convs
        new_cache["ssm_state"] = ssms

    else:
        m = cfg.moe
        has_layer0 = m is not None and m.first > 0 and "layer0" in params
        first = 1 if has_layer0 else 0
        if has_layer0:
            p0 = _cast(params["layer0"], cdt)
            h = rms_norm(x, p0["ln1"], cfg.norm_eps)
            h, kv0 = attn_decode(h, p0["attn"], 0)
            x = x + h
            h = rms_norm(x, p0["ln2"], cfg.norm_eps)
            x = x + swiglu(h, p0["mlp"]["w_gate"], p0["mlp"]["w_up"],
                           p0["mlp"]["w_down"])

        layers, experts = params["layers"], {}
        if m is not None:
            # the expert stacks stay whole, like the pool: the kernel reads
            # each layer's held experts in place through the layer index
            experts = _cast({n: layers["moe"][n] for n in ("w_gate", "w_up", "w_down")}, cdt)
            layers = dict(layers, moe={n: w for n, w in layers["moe"].items()
                                       if n not in experts})

        def layer_step(x1, xs):
            layer_p, layer = xs
            layer_p = _cast(layer_p, cdt)
            h = rms_norm(x1, layer_p["ln1"], cfg.norm_eps)
            h, kv = attn_decode(h, layer_p["attn"], layer)
            x1 = x1 + h
            h = rms_norm(x1, layer_p["ln2"], cfg.norm_eps)
            if m is not None:
                h = moe_decode(h, dict(layer_p["moe"], **experts), cfg,
                               layer=layer - first)
            else:
                ml = layer_p["mlp"]
                h = swiglu(h, ml["w_gate"], ml["w_up"], ml["w_down"])
            return x1 + h, kv

        x, kvs = lax.scan(layer_step, x,
                          (layers, jnp.arange(first, pool.shape[0])))
        if has_layer0:
            kvs = jnp.concatenate([kv0[None], kvs], axis=0)

    if kvs is not None:
        new_cache["kv_pool"] = _paged_kv_commit(pool, cache["block_table"],
                                                pos, kvs)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, x)
    new_cache["kv_len"] = pos + 1
    return logits, kvs, new_cache


# ================================================================= prefill
def prefill(params: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill forward: returns last-position logits (B, V) and aux.

    (The 32k-prefill dry-run shape measures the forward data path; cache
    materialization for serving reuses forward's per-layer K/V -- see
    launch/serve.py for the full pipeline.)
    """
    hidden, aux = forward(params, cfg, batch, remat=False)
    last = hidden[:, -1, :]
    return logits_from_hidden(params, cfg, last), aux
