"""Serving entry point: the model's paged decode step on the device, its KV
held by Taiji's elastic cache.

``python -m repro.launch.serve [--arch <id>] [--reduced]`` serves
``--n-seqs`` requests with seeded random weights, at the published widths
of ``--arch`` (default) or at its reduced CPU-test widths (``--reduced``):

* every request's prompt runs through the jitted decode step token by
  token, ``--batch`` requests per step;
* then ``--turns`` turns each schedule ``--batch`` requests, which decode
  ``--gen-len`` greedy tokens while their KV blocks are swapped in and
  pinned (the DMA pin contract);
* every K/V the step writes into the device pool is read back (bf16
  bytes) and appended to an :class:`ElasticKVCache` whose physical memory
  holds ``--kv-overcommit`` times fewer blocks than the requests' KV, so
  idle requests' blocks are swapped out and faulted back in;
* at the end, each request's Taiji-held KV is compared byte for byte with
  the device pool.

The schedule is drawn from ``--seed`` before the run, so every request's
final length -- and with it the live KV -- is known up front.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.reduce import reduced_config
from repro.core.config import (HotPathConfig, LRUConfig, SchedulerConfig,
                               SwapConfig)
from repro.core.elastic_kv import ElasticKVCache, KVGeometry, make_kv_taiji_config
from repro.core.system import TaijiSystem
from repro.models import model as M
from repro.models.config import ArchConfig


def serving_config(cfg: ArchConfig) -> ArchConfig:
    """Serving holds its weights in bf16 (float32 stays the training
    default): qwen3-4b's 4.0B parameters then take 8.0 GB of a 16 GB chip."""
    return dataclasses.replace(cfg, param_dtype="bfloat16")


def init_params(cfg: ArchConfig, seed: int) -> M.Params:
    """Seeded random weights, built on the device in one program."""
    return jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(seed), cfg)


def make_decode_step(cfg: ArchConfig):
    """Jitted decode step over a batch of the global KV pool.

    ``(params, tokens (B,), cache) -> (logits (B, V), greedy (B,),
    kv (B, L, 2, KV, hd), cache')``: ``cache`` holds the whole pool with
    the batch's block-table rows and lengths; it is donated, so the pool is
    updated in place. ``kv`` is what the step wrote into the pool.
    """
    bt = cfg.kv_block_tokens

    def step(params, tokens, cache):
        pos = cache["kv_len"]
        logits, new = M.decode_step(params, cfg, tokens, cache)
        blk = jnp.take_along_axis(cache["block_table"], (pos // bt)[:, None],
                                  axis=1)[:, 0]
        kv = new["kv_pool"][:, blk, pos % bt]           # (L, B, 2, KV, hd)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, jnp.moveaxis(kv, 0, 1), new

    return jax.jit(step, donate_argnums=(2,))


def _plan(n_seqs: int, turns: int, batch: int, seed: int):
    """The seeded schedule: which requests each turn decodes."""
    npr = np.random.default_rng(seed)
    return [npr.choice(n_seqs, size=batch, replace=False) for _ in range(turns)]


def run_serving(cfg: ArchConfig, params: M.Params, *, n_seqs: int,
                turns: int, batch: int, prompt_len: int, gen_len: int,
                kv_overcommit: float = 1.5, seed: int = 0,
                verbose: bool = True) -> Dict:
    """Serve the seeded requests; returns Taiji's stats and what the run
    checked (see the module docstring). The swap path runs the Pallas
    kernels.

    The fed tokens and per-step logits of the request the schedule
    decodes most (``trace_seq``) come back as ``tokens`` / ``logits``
    for :func:`check_against_reference`.
    """
    if (M.mamba_layer_count(cfg) or not cfg.causal or not cfg.n_heads
            or cfg.kv_pool_layout != "global"):
        raise ValueError(f"{cfg.name}: serving needs a causal attention-only "
                         f"decoder with the global KV pool layout")
    if n_seqs % batch:
        raise ValueError(f"n_seqs={n_seqs} is not a multiple of batch={batch}")
    bt = cfg.kv_block_tokens
    plan = _plan(n_seqs, turns, batch, seed)
    final_len = np.full(n_seqs, prompt_len)
    for ids in plan:
        final_len[ids] += gen_len
    blocks = -(-final_len // bt)
    live_blocks = int(blocks.sum())
    phys_blocks = int(live_blocks / kv_overcommit)
    pinned = max(int(blocks[ids].sum()) for ids in plan) if plan else 0
    if phys_blocks < pinned + 2:
        raise ValueError(f"{phys_blocks} physical KV blocks cannot pin a "
                         f"turn's {pinned} blocks; lower --kv-overcommit")
    max_seq = bt * int(blocks.max())
    trace_seq = int(np.argmax(final_len))

    geom = KVGeometry(n_layers=M.attn_layer_count(cfg),
                      kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
                      block_tokens=bt, dtype="bfloat16")
    tcfg = make_kv_taiji_config(
        geom, phys_blocks, overcommit=live_blocks / phys_blocks,
        lru=LRUConfig(scan_interval_s=0.002, workers=2, stabilize_scans=1),
        scheduler=SchedulerConfig(cycle_ms=2.0, shards=2),
        swap=SwapConfig(hot_path=HotPathConfig(pallas_kernels=True)))
    system = TaijiSystem(tcfg)
    try:
        system.start_background()
        kv_cache = ElasticKVCache(geom, system)
        out = _serve(cfg, params, kv_cache, plan, n_seqs=n_seqs, batch=batch,
                     prompt_len=prompt_len, gen_len=gen_len,
                     max_seq=max_seq, seed=seed, trace_seq=trace_seq)
        out.update(stats=system.stats(), live_blocks=live_blocks,
                   phys_blocks=phys_blocks, n_seqs=n_seqs,
                   trace_seq=trace_seq)
    finally:
        system.close()
    if verbose:
        m = out["stats"]["metrics"]
        print(f"served {n_seqs} requests: {live_blocks} live KV blocks over "
              f"{phys_blocks} physical; swapped out MS {m['ms_swapped_out']}, "
              f"swapped in MP {m['mp_swapped_in']}; fault latency "
              f"{m['fault_latency']}")
        print(f"decode steps {out['steps']}, first step (compile) "
              f"{out['compile_s']:.2f} s, the rest {out['run_s']:.2f} s; "
              f"turns' swap-in {out['swapin_s']:.2f} s; KV readback "
              f"{out['verify_s']:.2f} s, mismatched requests: "
              f"{out['kv_mismatched']}")
    return out


def _serve(cfg, params, kv_cache, plan, *, n_seqs, batch, prompt_len,
           gen_len, max_seq, seed, trace_seq) -> Dict:
    bt = cfg.kv_block_tokens
    step = make_decode_step(cfg)
    dev = M.init_cache(cfg, n_seqs, max_seq)
    pool, table = dev["kv_pool"], np.asarray(dev["block_table"])
    kv_len = np.zeros(n_seqs, np.int32)
    prompts = np.random.default_rng([seed, 1]).integers(
        0, cfg.vocab, (n_seqs, prompt_len), dtype=np.int32)
    next_tok = np.zeros(n_seqs, np.int32)
    fed, logits_trace, times = [], [], []

    def decode(ids, toks):
        nonlocal pool
        t0 = time.perf_counter()
        cache = {"kv_pool": pool, "block_table": jnp.asarray(table[ids]),
                 "kv_len": jnp.asarray(kv_len[ids])}
        logits, greedy, kv, new = step(params, jnp.asarray(toks), cache)
        pool = new["kv_pool"]
        kv = np.asarray(kv)           # blocks until the step is done
        for r, sid in enumerate(ids):
            kv_cache.append_kv(int(sid), kv[r])
        kv_len[ids] += 1
        next_tok[ids] = np.asarray(greedy)
        hit = np.flatnonzero(ids == trace_seq)
        if hit.size:
            fed.append(int(toks[hit[0]]))
            logits_trace.append(np.asarray(logits[hit[0]], np.float32))
        times.append(time.perf_counter() - t0)

    for sid in range(n_seqs):
        kv_cache.create_sequence(sid)
    for g in range(0, n_seqs, batch):          # prompts, token by token
        ids = np.arange(g, g + batch)
        for t in range(prompt_len):
            decode(ids, prompts[ids, t])
    swapin_s = 0.0
    for ids in plan:                           # turns of greedy decode
        t0 = time.perf_counter()
        with kv_cache.prepare_step(ids):       # swap-in + pin (DMA contract)
            swapin_s += time.perf_counter() - t0
            for _ in range(gen_len):
                decode(ids, next_tok[ids].copy())

    t0 = time.perf_counter()
    mismatched = []
    for sid in range(n_seqs):
        n = int(kv_len[sid])
        nb = -(-n // bt)
        dev_kv = np.asarray(pool[:, table[sid, :nb]])   # (L, nb, bt, 2, KV, hd)
        dev_kv = np.moveaxis(dev_kv, 0, 2).reshape(
            (nb * bt,) + dev_kv.shape[:1] + dev_kv.shape[3:])[:n]
        host_kv = kv_cache.read_blocks(sid)
        host_kv = host_kv.reshape((-1,) + host_kv.shape[2:])[:n]
        if dev_kv.tobytes() != host_kv.tobytes():
            mismatched.append(sid)
    return {"kv_mismatched": mismatched, "steps": len(times),
            "compile_s": times[0] if times else 0.0,
            "run_s": float(sum(times[1:])), "swapin_s": swapin_s,
            "verify_s": time.perf_counter() - t0,
            "tokens": np.asarray(fed, np.int32),
            "logits": np.stack(logits_trace) if logits_trace else None,
            "prompt_len": prompt_len}


def check_against_reference(cfg: ArchConfig, params: M.Params, out: Dict,
                            tol: float) -> Dict:
    """Compare the traced request's decode logits with ``forward`` on the
    same tokens. Greedy tokens agree where the decoded token's reference
    logit is within ``tol`` of the reference maximum (a tie within the
    tolerance); ``exact`` counts strict argmax agreement."""
    @jax.jit
    def forward_logits(params, tokens):
        hidden, _ = M.forward(params, cfg, {"tokens": tokens[None]}, remat=False)
        return M.logits_from_hidden(params, cfg, hidden)[0]

    ref = np.asarray(forward_logits(params, jnp.asarray(out["tokens"])),
                     np.float32)
    got = out["logits"]
    diff = float(np.max(np.abs(ref - got)))
    # the decoded token at position i+1 for every generated position
    gen = np.arange(out["prompt_len"] - 1, len(out["tokens"]) - 1)
    chosen = out["tokens"][gen + 1]
    gap = ref[gen].max(axis=-1) - ref[gen, chosen]
    return {"max_abs_diff": diff, "tol": tol, "positions": int(len(ref)),
            "greedy_checked": int(gen.size),
            "greedy_exact": int(np.sum(ref[gen].argmax(axis=-1) == chosen)),
            "greedy_agree": bool(np.all(gap <= tol)),
            "ok": bool(diff <= tol and np.all(gap <= tol))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's reduced CPU-test widths")
    ap.add_argument("--n-seqs", type=int, default=16)
    ap.add_argument("--turns", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=80)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--kv-overcommit", type=float, default=1.5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = serving_config(reduced_config(args.arch) if args.reduced
                         else get_config(args.arch))
    run_serving(cfg, init_params(cfg, args.seed), n_seqs=args.n_seqs,
                turns=args.turns, batch=args.batch,
                prompt_len=args.prompt_len, gen_len=args.gen_len,
                kv_overcommit=args.kv_overcommit, seed=args.seed)


if __name__ == "__main__":
    main()
