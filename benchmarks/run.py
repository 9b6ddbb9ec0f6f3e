"""Benchmark harness entry: one module per paper table/figure.

Prints ``name,value,derived`` CSV rows per the harness contract, plus the
full roofline table, and records every row in a ``BENCH_*.json`` artifact
(``BENCH_smoke.json`` for the CI perf canary, ``BENCH_full.json``
otherwise). ``python -m benchmarks.run [--quick] [--smoke]``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback


def _module_rows(mod, smoke: bool, trace_out=None):
    """Call ``mod.rows()``, passing ``smoke=`` / ``trace_out=`` only
    where supported."""
    params = inspect.signature(mod.rows).parameters
    kw = {}
    if smoke and "smoke" in params:
        kw["smoke"] = True
    if trace_out and "trace_out" in params:
        kw["trace_out"] = trace_out
    return mod.rows(**kw)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slower latency benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs, seconds not minutes (CI perf canary); "
                         "writes BENCH_smoke.json")
    ap.add_argument("--out", default=None,
                    help="JSON artifact path (default BENCH_smoke.json / "
                         "BENCH_full.json)")
    ap.add_argument("--trace-out", default=None,
                    help="write the fleet replay's stage spans as "
                         "Chrome-trace-event JSON (open in Perfetto / "
                         "chrome://tracing)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (backend_ratio, code_size, fault_latency, fleet,
                   lru_accuracy, metadata, overcommit, overhead, roofline)

    modules = [
        ("overhead (Fig 11/12)", overhead),
        ("metadata (Fig 13a)", metadata),
        ("overcommit (Fig 13b, §5.3.3)", overcommit),
        ("lru_accuracy (Fig 15b)", lru_accuracy),
        ("backend_ratio (Fig 15c)", backend_ratio),
        ("code_size (Table 2)", code_size),
        ("fleet (ISSUE 2/4: multi-node replay + chaos)", fleet),
    ]
    if not args.quick:
        # smoke mode keeps fault_latency (it carries the batched-vs-scalar
        # swap throughput rows the CI canary gates on) with a tiny config
        modules.insert(0, ("fault_latency (Fig 14f/15d)", fault_latency))

    print("name,value,derived")
    failures = 0
    recorded = {}
    for title, mod in modules:
        t0 = time.time()
        try:
            for name, value, derived in _module_rows(mod, args.smoke,
                                                     args.trace_out):
                print(f"{name},{value:.6g},{derived}")
                recorded[name] = {"value": float(value), "derived": str(derived)}
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# {title} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if not args.smoke:
        print("\n# === roofline table (from dry-run artifacts) ===")
        try:
            roofline.run(verbose=True)
        except Exception:
            failures += 1
            traceback.print_exc()

    out_path = args.out or ("BENCH_smoke.json" if args.smoke else "BENCH_full.json")
    payload = {
        "mode": "smoke" if args.smoke else ("quick" if args.quick else "full"),
        "failures": failures,
        "rows": recorded,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.abspath(out_path)}", file=sys.stderr)

    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
