"""Elastic paged KV cache -- Taiji applied to LLM serving.

The DPU analogy (DESIGN.md §2): a serving node statically reserves KV
space for its *maximum* concurrent sequences, but most sequences are idle
between turns -- exactly the paper's "reserved for peak, cold in practice"
memory. Taiji makes that reservation elastic:

  * one MS per (sequence, KV block): ``block_tokens`` tokens x all layers
    x K+V, so swap decisions happen at the paper's huge-page granularity
    while faults resolve at MP granularity;
  * idle sequences cool down in the multi-level LRU and get swapped to the
    zero/compressed backend by the watermark-driven reclaim task;
  * scheduling a sequence for decode = the DMA-range contract: its blocks
    are swapped in *before* the step and pinned while the step (the
    "no-retry DMA device") is in flight;
  * the device-side data plane reads KV through the block table inside the
    paged-attention kernel (kernels/paged_attention.py) -- the EPT walk on
    the I/O path.

All guest memory flows through one :class:`~.guest.GuestSpace` (the
sanctioned surface), so attaching a ``TraceRecorder`` to the space turns
a live serving workload into a replayable fleet trace with zero cache
changes.

Beyond-paper: ``prefetch_async`` overlaps the next batch's swap-ins with
the current step (double buffering), recorded in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Union

import ml_dtypes
import numpy as np

from ..analysis.lock_order import named_lock
from ..kernels.layout import ROW_BYTES
from ..obs.tracer import ST_KV_ALLOC, ST_KV_APPEND
from .config import ObsConfig, TaijiConfig
from .guest import GuestSpace
from .system import TaijiSystem
from .virt import F_SPLIT, NO_PFN

_perf_ns = time.perf_counter_ns


@dataclasses.dataclass(frozen=True)
class KVGeometry:
    n_layers: int
    kv_heads: int
    head_dim: int
    block_tokens: int = 16
    dtype: str = "float16"      # element type of the held K/V ("bfloat16" on device)

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(ml_dtypes.bfloat16 if self.dtype == "bfloat16"
                        else self.dtype)

    @property
    def dtype_bytes(self) -> int:
        return self.np_dtype.itemsize

    @property
    def block_bytes(self) -> int:
        # K and V for all layers of one block of tokens
        return (self.block_tokens * self.n_layers * 2 * self.kv_heads
                * self.head_dim * self.dtype_bytes)

    @property
    def tokens_per_block(self) -> int:
        return self.block_tokens


def _mpool_reserve_ms(ms_bytes: int, mps: int, n_phys: int,
                      overcommit: float) -> int:
    """Size the pinned arena for the virtual space with 2x headroom
    (the paper reserves 400 MB and reports <50% average utilization)."""
    n_virt = int(round((n_phys) * (1.0 + overcommit))) + 2
    per_gfn = (192 + 6 * mps) + 16          # MS record slab + table words
    need = 2 * (n_virt * per_gfn + 4 * ms_bytes)
    return max(2, -(-need // ms_bytes))


def make_kv_taiji_config(geom: KVGeometry, n_phys_blocks: int,
                         overcommit: float = 0.5, **overrides) -> TaijiConfig:
    """Size a Taiji config so one MS == one KV block.

    An MS splits into up to 8 MPs, each a multiple of 512 B where the
    block allows it (the row the swap kernels' word layout takes).
    Serving keeps the span tracer on (aggregates for ``render_prom`` and
    window deltas in ``Metrics.snapshot``); an ``obs=`` override wins."""
    ms_bytes = geom.block_bytes
    mps = next((m for m in (8, 4, 2) if ms_bytes % (m * ROW_BYTES) == 0), 1)
    reserve = _mpool_reserve_ms(ms_bytes, mps, n_phys_blocks, overcommit)
    base = dict(
        ms_bytes=ms_bytes,
        mps_per_ms=mps,
        n_phys_ms=n_phys_blocks + reserve,
        mpool_reserve_ms=reserve,
        overcommit_ratio=overcommit,
        obs=ObsConfig(enabled=True),
    )
    base.update(overrides)
    return TaijiConfig(**base)


class _PrefetchThread(threading.Thread):
    """Prefetch worker whose failures surface instead of dying silently:
    the exception is stored on the thread object and re-raised on
    ``join()`` (once the worker has actually finished)."""

    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        self.exc: Optional[BaseException] = None

    def run(self) -> None:
        try:
            super().run()
        except BaseException as e:      # noqa: BLE001 - surfaced on join
            self.exc = e

    def join(self, timeout: Optional[float] = None) -> None:
        super().join(timeout)
        if self.exc is not None and not self.is_alive():
            raise self.exc


class ElasticKVCache:
    """Host-side elastic KV block store for a serving node.

    Accepts either a :class:`GuestSpace` or a :class:`TaijiSystem` (its
    canonical ``.guest`` space is used), so capture/policy observers
    attached to the space see every cache operation.
    """

    def __init__(self, geom: KVGeometry,
                 space: Union[GuestSpace, TaijiSystem]) -> None:
        self.geom = geom
        self.space = space.guest if isinstance(space, TaijiSystem) else space
        self.system = self.space.system      # telemetry / legacy accessors
        # kv_append / kv_alloc spans; None when tracing is off
        self._tr = self.system.metrics.tracer
        self._lock = named_lock("app")
        # seq_id -> list of gfns (one per block) and token count
        self._blocks: Dict[int, List[int]] = {}
        self._tokens: Dict[int, int] = {}

    # ------------------------------------------------------------ sequences
    def create_sequence(self, seq_id: int) -> None:
        with self._lock:
            if seq_id in self._blocks:
                raise ValueError(f"sequence {seq_id} exists")
            self._blocks[seq_id] = []
            self._tokens[seq_id] = 0

    def drop_sequence(self, seq_id: int) -> None:
        with self._lock:
            gfns = self._blocks.pop(seq_id, [])
            self._tokens.pop(seq_id, None)
        for gfn in gfns:
            self.space.free_ms(gfn)

    def seq_len(self, seq_id: int) -> int:
        return self._tokens[seq_id]

    def blocks_of(self, seq_id: int) -> List[int]:
        return list(self._blocks[seq_id])

    # --------------------------------------------------------------- writes
    def append_kv(self, seq_id: int, kv_token: np.ndarray) -> None:
        """Append one token's KV: [n_layers, 2, kv_heads, head_dim], or its
        flat view [n_layers, 2 * kv_heads * head_dim] (the same bytes)."""
        g = self.geom
        expect = (g.n_layers, 2, g.kv_heads, g.head_dim)
        if kv_token.shape not in (expect, (g.n_layers, 2 * g.kv_heads * g.head_dim)):
            raise ValueError(f"kv shape {kv_token.shape} != {expect} or its flat view")
        tr = self._tr
        if tr is not None:
            t0 = _perf_ns()
        raw = kv_token.astype(g.np_dtype)
        with self._lock:
            t = self._tokens[seq_id]
            blocks = self._blocks[seq_id]
        slot = t % g.block_tokens
        if slot == 0:                      # new block needed
            if tr is not None:
                t_al = _perf_ns()
            gfn = self.space.alloc_ms()
            if tr is not None:
                tr.push(ST_KV_ALLOC, t_al, _perf_ns() - t_al)
            with self._lock:
                blocks.append(gfn)
        gfn = blocks[t // g.block_tokens]
        self.space.write(gfn, raw.tobytes(), off=slot * raw.nbytes)
        with self._lock:
            self._tokens[seq_id] = t + 1
        if tr is not None:
            tr.push(ST_KV_APPEND, t0, _perf_ns() - t0)

    # ---------------------------------------------------------------- reads
    def _block_dtype_shape(self):
        g = self.geom
        return g.np_dtype, (g.block_tokens, g.n_layers, 2, g.kv_heads, g.head_dim)

    def read_block(self, seq_id: int, block_idx: int) -> np.ndarray:
        """Read one block back as [block_tokens, n_layers, 2, kv_heads, head_dim]."""
        return self.read_blocks(seq_id, [block_idx])[0]

    def read_blocks(self, seq_id: int,
                    block_idxs: Optional[Sequence[int]] = None) -> np.ndarray:
        """Read several blocks of one sequence in a single batched gather
        (default: all of them): one residency probe, one observer
        dispatch, one ``[n_blocks, block_tokens, n_layers, 2, kv_heads,
        head_dim]`` result.  This is the attention hot path -- per-block
        ``view().load()`` paid the full translate/bounds/observer stack
        per block."""
        with self._lock:
            blocks = self._blocks[seq_id]
            gfns = (list(blocks) if block_idxs is None
                    else [blocks[i] for i in block_idxs])
        dt, shape = self._block_dtype_shape()
        return self.space.gather(gfns, dt, shape)

    # ------------------------------------------------------------- stepping
    def prepare_step(self, seq_ids: Sequence[int]):
        """Swap in + pin all blocks of the scheduled batch.

        Returns the DMA pin context; use ``with cache.prepare_step(b): step()``.
        Missing blocks are faulted in (this is where fault latency is paid
        and measured); pinned blocks cannot be reclaimed mid-step.
        """
        gfns: List[int] = []
        with self._lock:
            for sid in seq_ids:
                gfns.extend(self._blocks[sid])
        return self.space.pin(gfns)

    def prefetch_async(self, seq_ids: Sequence[int]) -> threading.Thread:
        """Beyond-paper: overlap next batch's swap-ins with the current step.

        Returns the worker thread; a failure inside the worker is stored
        on it and re-raised by ``join()`` rather than vanishing with the
        daemon thread.
        """
        with self._lock:
            gfns = [g for sid in seq_ids for g in self._blocks.get(sid, [])]
        system = self.space.system

        def work() -> None:
            # one vectorized residency probe over the whole candidate set
            # (only swapped or split MSs can need a swap-in) instead of a
            # req lookup per block; the watermark guard stays per-MS so a
            # long prefetch still yields to the pinned in-flight step
            g = np.asarray(gfns, dtype=np.int64)
            if not g.size:
                return
            table = system.virt.table
            cand = ((table.pfn[g] == NO_PFN)
                    | ((table.flags[g] & F_SPLIT) != 0))
            for gfn in (int(x) for x in g[cand]):
                # opportunistic: never compete with the pinned in-flight
                # step for the last free slots
                if system.phys.free_count <= system.watermark.low_ms:
                    return
                req = system.reqs.lookup(gfn)
                if req is not None and req.record.swapped_out_count() > 0:
                    system.engine.swap_in_ms(gfn)

        th = _PrefetchThread(target=work, name="kv-prefetch", daemon=True)
        th.start()
        return th

    # ------------------------------------------------------------ telemetry
    def residency(self) -> Dict[str, int]:
        with self._lock:
            all_gfns = [g for bl in self._blocks.values() for g in bl]
        res = self.space.residency(all_gfns)
        return {"resident_blocks": res["resident"],
                "swapped_blocks": res["swapped"],
                "total_blocks": res["total"]}
