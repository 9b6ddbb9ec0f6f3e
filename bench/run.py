"""Run one benchmark cell on the chip.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. One run loads and warms up (set-up), serves
the cell's traffic for ``--seconds`` (the window), checks what the
window served against the plain reference, and prints one JSON line
last on stdout: ``correct``, ``attempted`` and ``failed`` (requests),
``metrics`` (the end-to-end metrics with ``--trace 0``; the per-layer
ones with ``--trace 1``; those ``BENCHMARK.json`` declares for the cell), ``device`` and, traced, ``breakdown``; last
its ``checks``, each compared number beside its limit, which also end
stderr. It exits non-zero with no result when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind the peak table lacks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
OUT_DIR = BENCH / "out"
TRACE_SECONDS = 4.0


def use_compile_cache(jax) -> None:
    """JAX's persistent cache, fixed inside the checkout; it overrides
    any directory the environment names, so two checkouts share nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax, chips: int) -> dict:
    """The TPU devices JAX found; exits (code 2) on anything else."""
    from bench import counts

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"bench.run: JAX found platform {d.platform!r}, not a TPU")
    if len(devs) < chips:
        sys.exit(f"bench.run: the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        counts.peaks(d.device_kind)
    except KeyError as e:
        sys.exit(f"bench.run: {e}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def run_cell(cell, seed: int, seconds: float, trace: bool, *, device: dict,
             arch=None, step=None, after_setup=None, controls=(),
             t_start: float = None, trace_seconds: float = TRACE_SECONDS) -> dict:
    """One run of ``cell``; returns the result line's object, plus
    ``numbers``: everything compared and counted, printed on stderr.

    ``arch``, ``step`` and ``after_setup`` let tests run reduced widths
    and plant faults; ``controls`` also read the controls (calibration)."""
    import jax

    from bench import cell as cells
    from bench import check, counts, e2e, trace_reduce
    from bench.serving import ClosedLoop
    from bench.traffic.generator import SessionTraffic
    from bench.weights import make_params

    t_start = T_START if t_start is None else t_start
    arch = arch or cells.arch_config(cell.config, cell.root)
    params = jax.block_until_ready(make_params(arch, seed, cell.root))
    traffic = SessionTraffic(cell.traffic, seed, arch.vocab)
    loop = ClosedLoop(arch, params, traffic, step=step, after_setup=after_setup)
    trace_dir = None
    if trace:
        trace_dir = OUT_DIR / "trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    try:
        loop.setup()
        win = loop.run_window(seconds, str(trace_dir) if trace_dir else None,
                              trace_seconds)
        device = dict(device, memory_peak_bytes=memory_peak(jax))
        kv = loop.compare_kv()
    finally:
        loop.close()
    t_open, t_close = win["t_open"], win["t_close"]
    setup_s = t_open - t_start
    seen = e2e.counts(loop.requests, t_open, t_close)
    seen["percentiles"] = e2e.percentiles(loop.requests, t_open, t_close)
    taiji = {k: loop.stats_close[k] - loop.stats_open[k]
             for k in ("ms_swapped_out", "ms_swapped_in", "mp_swapped_in", "faults")}
    numbers = dict(kv, **seen, setup_s=setup_s, steps=len(loop.steps),
                   fault_latency_samples=int(loop.fault.count),
                   admissions=len(loop.admit_s),
                   compile_events_in_window=loop.compile_events,
                   taiji_in_window=taiji, physical_blocks=loop.sizing["physical"],
                   live_blocks_at_open=loop.sizing["live0"])

    result = {"correct": False, "attempted": seen["completed"], "failed": 0}
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(str(trace_dir))))
        rec = types.SimpleNamespace(
            arch=arch, cell=cell, loop=loop, trace=red,
            peaks=counts.peaks(device["kind"]) if red["chips"] else None,
            trace_span=loop.trace_s)
        metrics = {}
        readers = cells.metric_readers(cell.root)
        for name in cells.declared_metrics(cell.name, "per_layer", cell.root.parent):
            mod = readers[name]
            value = mod.read(rec)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": mod.UNIT}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]})
    else:
        values = e2e.metrics(loop.requests, t_open, t_close, setup_s)
        wanted = cells.declared_metrics(cell.name, "end_to_end", cell.root.parent)
        result.update(metrics={k: {"value": values[k], "unit": e2e.UNITS[k]}
                               for k in wanted},
                      device=device)

    picked = check.sample(loop.requests, t_close, seed)
    gaps = check.logit_gaps(cells.reference_module(cell.config, cell.root), cell.config,
                            params, loop.conv_tokens, picked, traffic.cap,
                            int(cell.traffic["output_tokens"]["hi"]), controls)
    numbers.update(gaps)
    v = check.verdict(numbers, cell.limits)
    result["correct"] = v["correct"]
    result["checks"] = v["compared"]
    result["numbers"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))     # the system under test
    import jax

    from bench.cell import load_cell

    cell = load_cell(args.workload)
    device = device_info(jax, cell.chips)
    use_compile_cache(jax)
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), device=device)
    numbers = res.pop("numbers")
    print("numbers " + json.dumps(numbers), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
