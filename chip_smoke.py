"""Smoke test of the serving main path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs from the root of a checkout, in one process, on the first TPU
device JAX finds, and exits non-zero before doing anything else when
that device is not a TPU. Weights and inputs come from ``--seed``.

(a) kernels: the four swap kernels on full-width qwen3-4b MPs
    (1,179,648 B rows) and ``paged_decode_attention`` at qwen3-4b widths,
    compiled (never interpreted), each checked against ``kernels/ref.py``;
(b) serving through ``repro.launch.serve.run_serving``: full-width
    qwen3-4b with bf16 weights, 12 requests whose KV (27 blocks of
    9 MiB) is 1.5x Taiji's physical blocks, the swap path on the Pallas
    kernels. It checks every request's Taiji-held KV byte for byte
    against the device pool, and one request's logits and greedy tokens
    against the full-sequence ``forward`` pass.

Each phase prints its device, compile seconds and run seconds. The last
line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every check passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

# decode (paged, one token at a time) vs forward (full sequence) on
# random bf16 weights: the same math in a different order, each rounded
# to bf16 at every layer (a logit quantum is 2^-5 at |logit| in [4, 8)).
# On a CPU at full width the gap grew from 0.088 (4 layers) to 0.125
# (12 layers), about as sqrt(depth): ~0.22 at 36 layers, so 0.5 leaves
# a 2x margin. Wrong KV is caught byte for byte, not by this bound.
LOGIT_TOL = 0.5
ATTN_TOL = 2e-2        # paged attention kernel vs the jnp oracle, bf16


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _line(phase: str, name: str, dev: dict, compile_s: float, run_s: float,
          ok: bool, **extra) -> bool:
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[{phase}] {name}: device={dev['platform']}:{dev['kind']} "
          f"compile_s={compile_s:.3f} run_s={run_s:.3f} "
          f"{'ok' if ok else 'FAILED'} {fields}".rstrip(), flush=True)
    return ok


def _timed(fn, *args):
    """AOT-compile ``fn`` for ``args``, run it once; (out, compile_s,
    run_s, compiled program text)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1, compiled.as_text()


def phase_kernels(dev: dict, seed: int) -> bool:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    assert not ops.interpret_mode()
    rng = np.random.default_rng(seed)
    mp_bytes, n = 9 * 1024 * 1024 // 8, 8          # full-width KV MPs
    rows = rng.integers(0, 256, (n, mp_bytes), dtype=np.uint8)
    rows[::3] = 0
    rows[4, -1] = 1                                 # nonzero in the last tile
    words = jnp.asarray(ops.to_words(rows))
    tile = ops.pick_tile_rows(words.shape[1])
    ok = True

    def check(name, got, want, text, c, r):
        nonlocal ok
        kernel = "tpu_custom_call" in text
        ok &= _line("a", name, dev, c, r, kernel and bool(np.all(got == want)),
                    compiled_kernel=kernel)

    got, c, r, text = _timed(lambda w: ops.zero_detect(
        w, tile_rows=tile, interpret=False), words)
    check("zero_detect", np.asarray(got), np.asarray(ref.zero_detect(rows)),
          text, c, r)
    got, c, r, text = _timed(lambda w: ops.fletcher_checksum(
        w, tile_rows=tile, interpret=False), words)
    check("fletcher_checksum", np.asarray(got),
          np.asarray(ref.fletcher_checksum(rows)), text, c, r)
    idx = rng.permutation(n)[:5].astype(np.int32)
    got, c, r, text = _timed(lambda p, i: ops.gather_blocks(
        p, i, interpret=False), words, jnp.asarray(idx))
    check("gather_blocks", ops.from_words(np.asarray(got), rows),
          np.asarray(ref.gather_blocks(rows, idx)), text, c, r)
    new = rng.integers(0, 256, (len(idx), mp_bytes), dtype=np.uint8)
    got, c, r, text = _timed(lambda p, i, b: ops.scatter_blocks(
        p, i, b, interpret=False), words, jnp.asarray(idx),
        jnp.asarray(ops.to_words(new)))
    check("scatter_blocks", ops.from_words(np.asarray(got), rows),
          np.asarray(ref.scatter_blocks(jnp.asarray(rows), idx, new)),
          text, c, r)

    B, H, KV, hd, bt, mbs = 4, 32, 8, 128, 64, 32   # qwen3-4b, 2048 tokens
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    pool = jnp.asarray(rng.standard_normal((B * mbs, bt, 2, KV, hd)),
                       jnp.bfloat16)
    table = jnp.asarray(rng.permutation(B * mbs).reshape(B, mbs), jnp.int32)
    kv_len = jnp.asarray(rng.integers(1, mbs * bt + 1, B), jnp.int32)
    got, c, r, text = _timed(lambda *a: ops.paged_decode_attention(
        *a, interpret=False), q, pool, table, kv_len)
    want = ref.paged_decode_attention(q, pool, table, kv_len)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    kernel = "tpu_custom_call" in text
    ok &= _line("a", "paged_decode_attention", dev, c, r,
                kernel and err <= ATTN_TOL, compiled_kernel=kernel,
                max_abs_err=err, tol=ATTN_TOL)
    return ok


def phase_serving(dev: dict, seed: int) -> bool:
    from repro.configs import get_config
    from repro.launch import serve

    cfg = serve.serving_config(get_config("qwen3-4b"))
    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, seed))
    init_s = time.perf_counter() - t0
    _line("b", "init_params", dev, init_s, 0.0, True,
          layers=cfg.n_layers, d_model=cfg.d_model, vocab=cfg.vocab,
          param_dtype=cfg.param_dtype)

    t0 = time.perf_counter()
    out = serve.run_serving(cfg, params, n_seqs=12, turns=6, batch=4,
                            prompt_len=96, gen_len=16, kv_overcommit=1.5,
                            seed=seed, verbose=False)
    wall_s = time.perf_counter() - t0
    m = out["stats"]["metrics"]
    served_ok = (not out["kv_mismatched"] and m["ms_swapped_out"] > 0
                 and m["mp_swapped_in"] > 0
                 and out["live_blocks"] >= 1.5 * out["phys_blocks"])
    ok = _line("b", "serving", dev, out["compile_s"], out["run_s"], served_ok,
               wall_s=f"{wall_s:.3f}", swapin_s=f"{out['swapin_s']:.3f}",
               verify_s=f"{out['verify_s']:.3f}",
               requests=out["n_seqs"], decode_steps=out["steps"],
               live_kv_blocks=out["live_blocks"],
               phys_kv_blocks=out["phys_blocks"],
               ms_swapped_out=m["ms_swapped_out"],
               mp_swapped_in=m["mp_swapped_in"],
               kv_mismatched_requests=len(out["kv_mismatched"]))

    t0 = time.perf_counter()
    res = serve.check_against_reference(cfg, params, out, LOGIT_TOL)
    ok &= _line("b", "decode_vs_forward", dev, time.perf_counter() - t0, 0.0,
                res["ok"], request=out["trace_seq"],
                positions=res["positions"],
                max_abs_logit_diff=res["max_abs_diff"], tol=LOGIT_TOL,
                greedy_checked=res["greedy_checked"],
                greedy_exact=res["greedy_exact"],
                greedy_agree=res["greedy_agree"])
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description="one-chip smoke test")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {dev['platform']!r} "
              f"({dev['kind']}), not a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    ok = phase_kernels(dev, args.seed)
    t1 = time.perf_counter()
    ok = phase_serving(dev, args.seed) and ok
    t2 = time.perf_counter()
    print(f"total_s={t2 - t0:.3f} kernels_s={t1 - t0:.3f} "
          f"serving_s={t2 - t1:.3f}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
