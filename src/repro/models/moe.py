"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Fine-grained MoE in the DeepSeekMoE style: ``n_shared`` always-on experts
plus ``n_routed`` routed experts with top-k gating. Dispatch is the
sort-based (dropping-above-capacity) formulation:

  1. top-k expert ids per token -> (T*k) assignments;
  2. stable-sort assignments by expert id;
  3. position-within-expert via searchsorted run starts;
  4. scatter token ids into an (E, C) slot table (overflow drops);
  5. grouped GEMM via einsum over the (E, C, D) gathered activations;
  6. combine: gather each assignment's output and weighted-sum over k.

Under pjit the sort/gather/scatter become XLA collectives when tokens are
data-sharded and experts are model-sharded (expert parallelism); the
roofline table attributes those bytes to the collective term.

A device may hold only some of the experts (``MoEConfig.held``, expert
parallelism's share): the router still scores all ``n_routed``, the
top-k gates are renormalised over the global top-k, and only the held
experts' part of the result is computed here; assignments to experts
held elsewhere add nothing. The decode step's expert layer
(:func:`moe_decode`) is dropless and runs the held experts through the
``expert_swiglu`` Pallas kernel, which reads only the experts that
received a token.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import shard_ctx
from repro.kernels import ops
from repro.kernels.expert_swiglu import ROW_TILE, expert_swiglu

from .config import ArchConfig, MoEConfig
from .layers import swiglu


def router_topk(x: jnp.ndarray, w_router: jnp.ndarray, top_k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (T, D) -> (gates (T,k), expert_idx (T,k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    E = w_router.shape[-1]
    me = jnp.mean(probs, axis=0)                       # mean router prob
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _held_ids(idx: jnp.ndarray, m: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Global expert ids (T, k) -> flat local ids among the held
    experts, with ``n_held`` for an expert held elsewhere, and the flat
    mask of held assignments."""
    lo, _ = m.held_range
    local = idx.reshape(-1) - lo
    held = (local >= 0) & (local < m.n_held)
    return jnp.where(held, local, m.n_held), held


def moe_decode(x: jnp.ndarray, p: dict, cfg: ArchConfig,
               layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The decode step's expert layer: x (T, D) -> (T, D), the held
    experts' part of it, dropless.

    Every assignment to a held expert is computed (no capacity). Each
    held expert's rows start on a row tile of the ``expert_swiglu``
    kernel, so an expert its rows reach is read once, and one no row
    reaches is not read; assignments to experts held elsewhere get no
    row. Shared experts are added as in :func:`moe_ffn`. With ``layer``,
    ``p``'s expert weights are the whole stack of layers, read in place
    at that index; without, one layer's."""
    m: MoEConfig = cfg.moe
    T, D = x.shape
    k, G = m.top_k, m.n_held
    gates, idx, _ = router_topk(x, p["router"], k)
    flat_e, held = _held_ids(idx, m)                  # (T*k,)
    token = jnp.arange(T * k) // k
    # each assignment's row: its expert's tile-aligned start + its rank
    onehot = flat_e[:, None] == jnp.arange(G + 1)[None, :]
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), flat_e[:, None], 1)[:, 0] - 1
    sizes = jnp.sum(onehot, 0)[:G].astype(jnp.int32)
    tiles = -(-sizes // ROW_TILE) * ROW_TILE
    start = jnp.cumsum(tiles) - tiles
    rows = -(-(T * min(k, G) + G * (ROW_TILE - 1)) // ROW_TILE) * ROW_TILE
    dest = jnp.where(held, start[jnp.minimum(flat_e, G - 1)] + rank, rows)
    xs = jnp.zeros((rows, D), x.dtype).at[dest].set(x[token], mode="drop")
    w = [p[n] if layer is not None else p[n][None]
         for n in ("w_gate", "w_up", "w_down")]
    y = expert_swiglu(xs, *w, tiles, 0 if layer is None else layer,
                      interpret=ops.interpret_mode())
    y = y[jnp.minimum(dest, rows - 1)].astype(jnp.float32)
    contrib = jnp.where(held[:, None], y * gates.reshape(-1)[:, None], 0.0)
    out = jnp.zeros((T, D), jnp.float32).at[token].add(contrib)
    if m.n_shared:
        out = out + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"]).astype(jnp.float32)
    return out.astype(x.dtype)


def _dispatch_tokens(xt: jnp.ndarray, p: dict, cfg: ArchConfig,
                     constrain: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based dispatch + grouped GEMM over a flat token set.

    xt: (T, D) -> (out (T, D) fp32, aux scalar).
    """
    m: MoEConfig = cfg.moe
    T, D = xt.shape
    k = m.top_k
    # capacity with a dropless floor for small token counts (decode steps
    # are exact; large training/prefill batches use capacity-factor drops)
    C = min(max(int(T * k / m.n_routed * m.capacity_factor), 64), T)

    gates, idx, aux = router_topk(xt, p["router"], k)

    # ---- sort assignments by held expert (others last, dropped) ---------
    flat_e, _ = _held_ids(idx, m)                     # (T*k,)
    E = m.n_held
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within each expert's run
    starts = jnp.searchsorted(se, jnp.arange(E + 1), side="left")
    pos = jnp.arange(T * k) - starts[se]
    keep = (pos < C) & (se < E)

    # ---- scatter into the (E, C) slot table ----------------------------
    slot = jnp.where(keep, se * C + pos, E * C)       # drops -> scratch slot
    token_for_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        st.astype(jnp.int32), mode="drop")[: E * C]
    # gather activations; token id T -> zero row
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    c_or_id = shard_ctx.moe_dispatch if constrain else (lambda t: t)
    xe = c_or_id(xt_pad[token_for_slot].reshape(E, C, D))

    # ---- grouped expert GEMMs ------------------------------------------
    h = c_or_id(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    u = c_or_id(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
    h = jax.nn.silu(h) * u
    ye = c_or_id(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))   # (E, C, D)

    # ---- combine back to tokens ----------------------------------------
    ye_flat = jnp.concatenate(
        [ye.reshape(E * C, D), jnp.zeros((1, D), ye.dtype)], axis=0)
    # for each sorted assignment: its slot output (dropped -> zero row).
    # Combine in the compute dtype: an fp32 accumulator here upcasts the
    # whole dispatch exchange (fwd + bwd) to fp32 -- measured as 2x the EP
    # all-to-all bytes on qwen3-moe train (EXPERIMENTS.md §Perf cell B).
    # Each token sums exactly top_k contributions, safe in bf16.
    contrib = ye_flat[jnp.where(keep, se * C + pos, E * C)]
    out = jnp.zeros((T + 1, D), xt.dtype).at[st].add(
        (contrib.astype(jnp.float32) * sg[:, None]).astype(xt.dtype),
        mode="drop")[:T]
    return out, aux


def moe_ffn(x: jnp.ndarray, p: dict, cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss).

    p: router (D, E); w_gate/w_up (E, D, F); w_down (E, F, D);
       optional shared_{gate,up,down} for the shared experts.

    Two dispatch modes (EXPERIMENTS.md §Perf cell B):
      * global: one sort over all B*S tokens (baseline). Correctness-
        simple, but under pjit the global argsort/scatter of data-sharded
        tokens compiles to cross-device collective chains per layer.
      * grouped: vmap the same dispatch over per-sample groups (B groups
        of S tokens). Sorts become shard-local; the remaining collective
        is the unavoidable expert-parallel (group -> expert) exchange.
    """
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    grouped = m.grouped_dispatch and B > 1 and S >= m.min_group_tokens

    if grouped:
        # no in-group constraints: the vmapped group dim carries the data
        # sharding; constraining (E, C, D) inside vmap would shard C over
        # the batch axis and replicate groups (measured regression)
        outs, auxs = jax.vmap(
            lambda xg: _dispatch_tokens(xg, p, cfg, constrain=False))(x)
        out = outs.reshape(B * S, D)
        aux = jnp.mean(auxs)
    else:
        out, aux = _dispatch_tokens(x.reshape(B * S, D), p, cfg)

    # ---- shared experts (dense, always on) ------------------------------
    xt = x.reshape(B * S, D)
    if m.n_shared:
        g = jnp.einsum("td,df->tf", xt, p["shared_gate"])
        u2 = jnp.einsum("td,df->tf", xt, p["shared_up"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u2,
                               p["shared_down"]).astype(out.dtype)

    return out.reshape(B, S, D).astype(x.dtype), aux * m.router_aux_weight
