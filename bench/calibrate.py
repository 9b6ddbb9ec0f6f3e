"""Readings that the cells' limits are set from, at the cells' own size.

    python -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 8 \
        [--controls int8,fp8]

runs the cell once per seed in one process (set-up, a short window at
the cell's own load, the check) and prints one JSON line per seed: the
program's ``logit_err_rms``, ``logit_err_max`` and ``logit_gap_max``, each control's (the
reference one precision step below bfloat16, read at the same
positions), the KV comparison, and ``correct`` of the program and of
each control against the cell file's limits. The
benchmark's own runs never run the controls.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench.run import ROOT, device_info, run_cell, use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import check
    from bench.cell import load_cell

    cell = load_cell(args.workload)
    device = device_info(jax, cell.chips)
    use_compile_cache(jax)
    controls = tuple(c for c in args.controls.split(",") if c)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, device=device,
                       controls=controls, t_start=t0)
        n = res["numbers"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "logit_err_rms": n["logit_err_rms"],
                          "logit_err_max": n["logit_err_max"],
                          "logit_gap_max": n["logit_gap_max"],
                          "control": n["control"],
                          "control_correct": {
                              q: check.verdict(check.control_numbers(n, q),
                                               cell.limits)["correct"]
                              for q in controls},
                          "kv_mismatch_bytes": n["kv_mismatch_bytes"],
                          "served_tokens_checked": n["served_tokens_checked"],
                          "requests_checked": n["requests_checked"],
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
