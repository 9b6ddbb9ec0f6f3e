"""A cell's driver: the program's serving pieces, in a closed loop.

The program has no request loop yet; ``repro.launch.serve._serve``
runs a fixed plan. This driver puts together the same pieces that
``_serve`` uses -- ``serve.make_decode_step`` over the pool that
``M.init_cache`` builds, an ``ElasticKVCache`` on a Taiji system sized
by ``make_kv_taiji_config`` with the swap path on the Pallas kernels,
``prepare_step`` at admission, ``append_kv`` after each step's K/V
readback -- and adds only admission per row and the clocks.

``clients`` rows each keep one request (one turn of a session) in
flight. A request pins its session's blocks at admission, feeds its
user tokens through the decode step one by one, then decodes its output
tokens greedily; when it finishes its row is admitted again at once,
on a session drawn from the idle ones. Each generated token keeps the
step's ``TOP_K`` largest logits, for the check. The harness's spans
around each call into the program are ``jax.profiler`` annotations
(``bench.*``).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.config import (HotPathConfig, LRUConfig, SchedulerConfig,
                               SwapConfig, WatermarkConfig)
from repro.core.elastic_kv import ElasticKVCache, KVGeometry, make_kv_taiji_config
from repro.core.system import TaijiSystem
from repro.launch import serve
from repro.models import model as M

from .traffic.generator import SessionTraffic

# the Taiji background settings of serve.run_serving
LRU = LRUConfig(scan_interval_s=0.002, workers=2, stabilize_scans=1)
SCHED = SchedulerConfig(cycle_ms=2.0, shards=2)
WARM_STEPS = 8           # closed-loop steps run before the window opens
TOP_K = 8                # logits kept per generated token


@jax.jit
def top_logits(logits: jax.Array):
    """The ``TOP_K`` largest logits of each row, in float32, and their
    token ids."""
    return jax.lax.top_k(logits.astype(jnp.float32), TOP_K)


def taiji_sizing(traffic: SessionTraffic, block_tokens: int) -> Dict[str, int]:
    """Physical KV blocks for the mix: the seeded live blocks over
    ``kv_live_over_physical``, or, where that is null, every session at
    the context cap above the high watermark (nothing is reclaimed)."""
    cap_blocks = traffic.cap // block_tokens
    live0 = int(np.sum(-(-traffic.histories // block_tokens)))
    ratio = traffic.mix["kv_live_over_physical"]
    pin_max = traffic.clients * cap_blocks
    if ratio is None:
        phys = int(np.ceil(traffic.sessions * cap_blocks
                           / (1.0 - WatermarkConfig().high))) + 1
    else:
        phys = int(live0 / float(ratio))
        if phys < pin_max + 2:
            raise ValueError(f"{phys} physical blocks cannot pin {traffic.clients}"
                             f" sessions at the cap ({pin_max} blocks); lower clients")
    return {"physical": phys, "live0": live0, "cap_blocks": cap_blocks,
            "virtual_max": traffic.sessions * cap_blocks}


class ClosedLoop:
    """Set-up, window and check-out of one run; see the module docstring.

    ``step`` replaces the program's decode step (tests plant faults
    through it and through ``after_setup``).
    """

    def __init__(self, arch, params, traffic: SessionTraffic, *,
                 step: Optional[Callable] = None,
                 after_setup: Optional[Callable] = None) -> None:
        self.arch, self.params, self.traffic = arch, params, traffic
        self.bt = arch.kv_block_tokens
        self.step = step or serve.make_decode_step(arch)
        self.after_setup = after_setup
        self.sizing = taiji_sizing(traffic, self.bt)
        self.swaps = traffic.mix["kv_live_over_physical"] is not None
        S, cap = traffic.sessions, traffic.cap
        dev = M.init_cache(arch, S, cap)
        self.pool = dev["kv_pool"]
        self.table = np.asarray(dev["block_table"])
        self.kv_len = np.zeros(S, np.int32)
        self.geom = KVGeometry(n_layers=M.attn_layer_count(arch),
                               kv_heads=arch.n_kv_heads, head_dim=arch.head_dim_,
                               block_tokens=self.bt, dtype="bfloat16")
        phys = self.sizing["physical"]
        tcfg = make_kv_taiji_config(
            self.geom, phys,
            overcommit=max(0.5, self.sizing["virtual_max"] / phys),
            lru=LRU, scheduler=SCHED,
            swap=SwapConfig(hot_path=HotPathConfig(pallas_kernels=True)))
        self.tcfg = tcfg
        self.system = TaijiSystem(tcfg)
        self.kv_cache = ElasticKVCache(self.geom, self.system)
        # per session: its conversation (an index into conv_tokens)
        self.conv_of = np.zeros(S, np.int64)
        self.conv_tokens: List[List[int]] = []
        self.idle = np.ones(S, bool)
        self.ended = np.zeros(S, bool)        # the conversation's last turn was served
        self.rows: List[Optional[Dict]] = [None] * traffic.clients
        self.next_tok = np.zeros(traffic.clients, np.int32)
        self.requests: List[Dict] = []
        self.steps: List[Dict] = []           # per window step
        self.admit_s: List[float] = []        # prepare_step, per admission in window
        self.in_window = False
        self._pins = {}

    # ---------------------------------------------------------------- set-up
    def warm_swap_kernels(self) -> None:
        """Compile (or load) every swap-kernel shape the swap path can
        call: 1..mps_per_ms MPs of one MS."""
        from repro.kernels import ops

        mps, mp = self.tcfg.mps_per_ms, self.tcfg.mp_bytes
        frame = np.zeros((mps, mp), np.uint8)
        for n in range(1, mps + 1):
            rows = frame[:n]
            idx = np.arange(n, dtype=np.int32)
            ops.batch_zero_detect(rows)
            ops.batch_checksum(rows)
            ops.batch_gather(frame, idx)
            ops.batch_scatter(frame, idx, rows)

    def build_histories(self) -> None:
        """Feed every session's seeded history through the decode step at
        a batch of all sessions, appending each step's K/V to Taiji."""
        tr = self.traffic
        hist = tr.history_tokens()
        S = tr.sessions
        for s in range(S):
            self.kv_cache.create_sequence(s)
            self.conv_of[s] = len(self.conv_tokens)
            self.conv_tokens.append([int(t) for t in hist[s, :tr.histories[s]]])
        for t in range(int(tr.histories.max())):
            active = np.flatnonzero(tr.histories > t)
            tok = np.where(tr.histories > t, hist[:, t], 0).astype(np.int32)
            cache = {"kv_pool": self.pool,      # donated, as a whole
                     "block_table": jnp.asarray(self.table),
                     "kv_len": jnp.asarray(self.kv_len)}
            _, _, kv, new = self.step(self.params, jnp.asarray(tok), cache)
            self.pool = new["kv_pool"]
            kv = np.asarray(kv)
            for s in active:
                self.kv_cache.append_kv(int(s), kv[s])
            self.kv_len[active] += 1

    def setup(self) -> None:
        self.system.start_background()
        if self.swaps:
            self.warm_swap_kernels()
        self.build_histories()
        now = time.perf_counter()
        for r in range(self.traffic.clients):
            self._admit(r, now)
        for _ in range(WARM_STEPS):
            self._step()
        if self.after_setup is not None:
            self.after_setup(self)

    # ------------------------------------------------------------- the loop
    def _admit(self, r: int, t_send: float) -> None:
        tr = self.traffic
        s = tr.pick_session(np.flatnonzero(self.idle))
        U, G, ends = tr.request()
        if self.ended[s] or self.kv_len[s] + U + G - 1 > tr.cap:   # a new conversation
            self.kv_cache.drop_sequence(s)
            self.kv_cache.create_sequence(s)
            self.kv_len[s] = 0
            self.conv_of[s] = len(self.conv_tokens)
            self.conv_tokens.append([])
        self.ended[s] = ends
        user = tr.tokens(U)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.admit"):
            pin = self.kv_cache.prepare_step([s])
            pin.__enter__()
        if self.in_window:
            self.admit_s.append(time.perf_counter() - t0)
        self._pins[r] = pin
        self.idle[s] = False
        req = {"session": s, "conv": int(self.conv_of[s]),
               "p0": int(self.kv_len[s]), "U": U, "G": G, "user": user,
               "fed": 0, "gen": [], "top": [], "t_send": t_send, "t_tokens": [],
               "t_done": None}
        self.rows[r] = req
        self.requests.append(req)
        self.next_tok[r] = user[0]

    def _release(self, r: int) -> None:
        req = self.rows[r]
        self._pins.pop(r).__exit__(None, None, None)
        self.idle[req["session"]] = True
        self.rows[r] = None

    def _step(self) -> None:
        sess = np.array([req["session"] for req in self.rows])
        tok = self.next_tok.copy()
        kv_lens = self.kv_len[sess]
        t0 = time.perf_counter()
        with TraceAnnotation("bench.step_dispatch"):
            cache = {"kv_pool": self.pool,
                     "block_table": jnp.asarray(self.table[sess]),
                     "kv_len": jnp.asarray(kv_lens)}
            logits, greedy, kv, new = self.step(self.params, jnp.asarray(tok), cache)
            self.pool = new["kv_pool"]
            top_v, top_i = top_logits(logits)
        with TraceAnnotation("bench.readback"):
            kv = np.asarray(kv)
            greedy = np.asarray(greedy)
            top_v, top_i = np.asarray(top_v), np.asarray(top_i)
        t_ready = time.perf_counter()
        with TraceAnnotation("bench.append_kv"):
            for r, s in enumerate(sess):
                self.kv_cache.append_kv(int(s), kv[r])
        t_app = time.perf_counter()
        self.kv_len[sess] += 1
        if self.in_window:
            self.steps.append({"t0": t0, "t_ready": t_ready,
                               "append_s": t_app - t_ready,
                               "kv_lens": kv_lens})
        done = []
        for r, req in enumerate(self.rows):
            self.conv_tokens[req["conv"]].append(int(tok[r]))
            req["fed"] += 1
            if req["fed"] < req["U"]:
                self.next_tok[r] = req["user"][req["fed"]]
                continue
            req["gen"].append(int(greedy[r]))
            req["top"].append((top_v[r], top_i[r]))
            req["t_tokens"].append(t_ready)
            self.next_tok[r] = greedy[r]
            if len(req["gen"]) == req["G"]:
                req["t_done"] = t_ready
                done.append(r)
        for r in done:
            self._release(r)
        now = time.perf_counter()
        for r in done:
            self._admit(r, now)

    def run_window(self, seconds: float, trace_dir: Optional[str] = None,
                   trace_seconds: float = 0.0) -> Dict[str, float]:
        """Serve for ``seconds``; trace the last ``trace_seconds`` of the
        window (steady state) into ``trace_dir`` when given."""
        self.system.metrics.reset_fault_latency()
        self.stats_open = self.system.stats()["metrics"]
        self.swapin_n0 = len(self.system.metrics.swap_in_latency.samples)
        self.compile_events = 0

        def on_event(name: str, *_args, **_kw) -> None:
            if name.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
                self.compile_events += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        self.in_window = True
        t_open = time.perf_counter()
        t_close = t_open + seconds
        t_trace = t_close - trace_seconds if trace_dir else None
        span = None
        while time.perf_counter() < t_close:
            if t_trace is not None and span is None and time.perf_counter() >= t_trace:
                jax.profiler.start_trace(trace_dir)
                span = TraceAnnotation("bench.trace_window")
                span.__enter__()
                t_trace = time.perf_counter()
            self._step()
        if span is not None:
            self.trace_s = (t_trace, time.perf_counter())
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.in_window = False
        jax.monitoring.unregister_event_duration_listener(on_event)
        self.stats_close = self.system.stats()["metrics"]
        self.fault = self.system.metrics.fault_latency
        return {"t_open": t_open, "t_close": t_close}

    # ------------------------------------------------------------ check-out
    def release_all(self) -> None:
        for r in range(len(self.rows)):
            if self.rows[r] is not None:
                self._release(r)

    def compare_kv(self) -> Dict[str, int]:
        """Every session's Taiji-held K/V, read back through the cache
        (faulting swapped blocks in), against the device pool, byte for
        byte."""
        self.release_all()
        pool = np.asarray(self.pool)          # (L, blocks, bt, 2, KV, hd)
        bad_bytes = bad_sessions = checked = 0
        for s in range(self.traffic.sessions):
            n = int(self.kv_len[s])
            if n == 0:
                continue
            nb = -(-n // self.bt)
            rows = self.table[s, :nb]
            dev = np.moveaxis(pool[:, rows], 0, 2)
            dev = dev.reshape((nb * self.bt,) + dev.shape[2:])[:n]
            host = self.kv_cache.read_blocks(s)
            host = host.reshape((-1,) + host.shape[2:])[:n]
            a = np.frombuffer(dev.tobytes(), np.uint8)
            b = np.frombuffer(host.tobytes(), np.uint8)
            diff = int(np.count_nonzero(a != b)) if a.size == b.size else max(a.size, b.size)
            bad_bytes += diff
            bad_sessions += diff > 0
            checked += n
        del pool
        return {"kv_mismatch_bytes": bad_bytes, "kv_mismatch_sessions": bad_sessions,
                "kv_tokens_checked": checked}

    def close(self) -> None:
        self.release_all()
        self.system.close()
        self.pool = None
