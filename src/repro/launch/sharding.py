"""Sharding rules: parameter / optimizer / batch / cache PartitionSpecs.

Scheme (DESIGN.md §5):
  * ``model`` axis = tensor parallel (attention heads, FFN hidden, Mamba
    d_inner, vocab for the LM head, MoE expert dim = expert parallel);
  * ``data`` axis = batch data-parallel + ZeRO-3 FSDP on parameters and
    optimizer state (sharded on d_model-sized dims, all-gathered per
    scanned layer);
  * ``pod`` axis (multi-pod mesh) = outer data parallel: batch sharded
    over (pod, data), parameters replicated across pods (baseline; the
    §Perf log explores FSDP over pods).

Every rule is divisibility-guarded: an axis is only assigned if it evenly
divides the dim, so one rule set serves all ten archs (e.g. 14-head
qwen2-0.5b simply leaves heads unsharded on a 16-way model axis).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free mesh for rule code and tests."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def axis_size(mesh: Mesh, name: Optional[str]) -> int:
    return mesh.shape[name] if name and name in mesh.shape else 1


class ShardingRules:
    def __init__(self, cfg: ArchConfig, mesh: Mesh,
                 tp_axis: str = "model", fsdp_axis: str = "data",
                 pod_axis: Optional[str] = None,
                 fsdp_over_pod: bool = False) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.tp = tp_axis
        self.fsdp = fsdp_axis if fsdp_axis in mesh.shape else None
        self.pod = pod_axis if (pod_axis and pod_axis in mesh.shape) else None
        # batch shards over (pod, data)
        if self.pod:
            self.batch_axes: Any = (self.pod, fsdp_axis)
        else:
            self.batch_axes = fsdp_axis
        # beyond-paper option: FSDP over (pod, data) instead of data only
        self.fsdp_spec = ((self.pod, self.fsdp) if (fsdp_over_pod and self.pod)
                          else self.fsdp)

    # -------------------------------------------------------------- helpers
    def _fit(self, dim: int, axis) -> Optional[Any]:
        """Assign ``axis`` to a dim only if it divides evenly."""
        if axis is None:
            return None
        if isinstance(axis, tuple):
            total = 1
            for a in axis:
                if a is None:
                    return None
                total *= axis_size(self.mesh, a)
            return axis if dim % total == 0 else self._fit(dim, axis[-1])
        return axis if dim % axis_size(self.mesh, axis) == 0 else None

    def _spec(self, shape: Tuple[int, ...], *last_dims) -> P:
        """Right-aligned spec: assign rules to the trailing dims."""
        lead = len(shape) - len(last_dims)
        entries = [None] * lead
        for i, axis in enumerate(last_dims):
            entries.append(self._fit(shape[lead + i], axis))
        return P(*entries)

    # ------------------------------------------------------------ parameters
    def param_pspecs(self, param_shapes) -> Any:
        cfg = self.cfg
        tp, fsdp = self.tp, self.fsdp_spec

        def tp_if(cond):
            return tp if cond else None

        tp_size = axis_size(self.mesh, tp)
        tp_q = tp_if(cfg.n_heads and cfg.n_heads % tp_size == 0)
        tp_kv = tp_if(cfg.n_kv_heads and cfg.n_kv_heads % tp_size == 0)
        tp_ep = None
        if cfg.moe is not None and cfg.moe.n_held % tp_size == 0:
            tp_ep = tp

        def rule(path, leaf) -> P:
            keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
            name = keys[-1]
            shape = leaf.shape
            in_moe = "moe" in keys or name.startswith("shared_")

            if name == "embed":
                if cfg.tie_embeddings:
                    # tied: keep vocab-major so the logits matmul comes out
                    # vocab-sharded (Megatron-style vocab parallelism)
                    return self._spec(shape, tp, None)
                # d_model over the model axis: the token-gather output then
                # reshards with one small all-gather instead of the SPMD
                # partitioner's involuntary full replication (multi-pod)
                return self._spec(shape, None, tp)
            if name == "lm_head":
                return self._spec(shape, fsdp, tp)
            if name == "frontend_proj":
                return self._spec(shape, None, fsdp)
            if name in ("final_norm",) or name.startswith("ln"):
                return P(*([None] * len(shape)))
            # attention
            if name == "wq":
                return self._spec(shape, fsdp, tp_q)
            if name in ("wk", "wv"):
                return self._spec(shape, fsdp, tp_kv)
            if name == "wo":
                return self._spec(shape, tp_q, fsdp)
            if name == "bq":
                return self._spec(shape, tp_q)
            if name in ("bk", "bv"):
                return self._spec(shape, tp_kv)
            if name in ("q_norm", "k_norm"):
                return P(*([None] * len(shape)))
            # MoE
            if name == "router":
                return self._spec(shape, fsdp, None)
            if in_moe and name in ("w_gate", "w_up"):
                return self._spec(shape, tp_ep, fsdp, None)
            if in_moe and name == "w_down":
                return self._spec(shape, tp_ep, None, fsdp)
            if name in ("shared_gate", "shared_up"):
                return self._spec(shape, fsdp, tp)
            if name == "shared_down":
                return self._spec(shape, tp, fsdp)
            # dense MLP
            if name in ("w_gate", "w_up"):
                return self._spec(shape, fsdp, tp)
            if name == "w_down":
                return self._spec(shape, tp, fsdp)
            # mamba
            if name == "in_proj":
                return self._spec(shape, fsdp, tp)
            if name == "conv_w":
                return self._spec(shape, None, tp)
            if name in ("conv_b", "dt_bias", "D"):
                return self._spec(shape, tp)
            if name == "x_proj":
                return self._spec(shape, tp, None)
            if name == "dt_proj":
                return self._spec(shape, None, tp)
            if name == "A_log":
                return self._spec(shape, tp, None)
            if name == "out_proj":
                return self._spec(shape, tp, fsdp)
            return P(*([None] * len(shape)))

        return jax.tree_util.tree_map_with_path(rule, param_shapes)

    def state_pspecs(self, state_shapes) -> Any:
        """TrainState specs: step replicated; params/opt share param rules."""
        params = self.param_pspecs(state_shapes.params)
        return type(state_shapes)(
            step=P(),
            params=params,
            opt=type(state_shapes.opt)(mu=params, nu=params),
        )

    # ----------------------------------------------------------------- data
    def batch_pspecs(self, batch_shapes: Dict[str, Any]) -> Dict[str, P]:
        out = {}
        for k, v in batch_shapes.items():
            if k == "mrope_pos":        # (3, B, S)
                out[k] = P(None, self._fit(v.shape[1], self.batch_axes), None)
            else:                        # (B, ...) leading batch
                out[k] = P(self._fit(v.shape[0], self.batch_axes),
                           *([None] * (len(v.shape) - 1)))
        return out

    def cache_pspecs(self, cache_shapes: Dict[str, Any], batch: int) -> Dict[str, P]:
        cfg = self.cfg
        tp_size = axis_size(self.mesh, self.tp)
        tp_di = self.tp if (cfg.d_inner and cfg.d_inner % tp_size == 0) else None
        # batch too small to shard (long_500k B=1): shard blocks over data
        b_ax = self._fit(batch, self.batch_axes)
        out: Dict[str, P] = {}
        for k, v in cache_shapes.items():
            if k == "kv_pool":
                if len(v.shape) == 7:    # per_seq: (La, B, mbs, bt, 2, KV, hd)
                    out[k] = P(None, self._fit(v.shape[1], self.batch_axes),
                               None, None, None, None, None)
                else:                    # global: (La, NB, bt, 2*KV*hd)
                    out[k] = P(None, self._fit(v.shape[1], self.batch_axes),
                               None, None)
            elif k == "block_table":    # (B, mbs)
                out[k] = P(b_ax, None)
            elif k == "kv_len":         # (B,)
                out[k] = P(b_ax)
            elif k == "conv_state":     # (Lm, B, dc-1, DI)
                out[k] = P(None, b_ax, None, tp_di)
            elif k == "ssm_state":      # (Lm, B, DI, DS)
                out[k] = P(None, b_ax, tp_di, None)
            else:
                out[k] = P(*([None] * len(v.shape)))
        return out

    # -------------------------------------------------------------- helpers
    def named(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def make_axis_ctx(self, batch: Optional[int] = None):
        """Activation-sharding context for model-internal constraints."""
        from repro import shard_ctx
        cfg = self.cfg
        tp_size = axis_size(self.mesh, self.tp)
        batch_axes = self.batch_axes
        if batch is not None and self._fit(batch, batch_axes) is None:
            batch_axes = None
        return shard_ctx.AxisCtx(
            batch=batch_axes,
            tp=self.tp,
            heads_ok=bool(cfg.n_heads and cfg.n_heads % tp_size == 0),
            kv_heads_ok=bool(cfg.n_kv_heads and cfg.n_kv_heads % tp_size == 0),
            vocab_ok=cfg.vocab % tp_size == 0,
            d_inner_ok=bool(cfg.d_inner and cfg.d_inner % tp_size == 0),
            experts_ok=bool(cfg.moe is not None
                            and cfg.moe.n_held % tp_size == 0),
            ffn_ok=bool(cfg.d_ff and cfg.d_ff % tp_size == 0),
        )
